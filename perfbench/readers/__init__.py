"""One small reader a per-layer metric: read(ctx, params) -> number | None.
ctx holds what the run recorded: "trace", "lo", "hi" (the reduced profiler
trace and its window), "run" (the adapter's counts and times), "cell",
"peaks", "chips". A reader that finds nothing to read returns None."""
