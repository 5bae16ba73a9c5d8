"""How a kind of cell is set up, driven and compared. An adapter is a module
with run(cell, seed, seconds, trace, platform="tpu") -> result dict."""
