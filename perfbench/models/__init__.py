"""One module a model family, found by the `family` key of a configuration:
`build(cfg, cell)` makes the program's own model through its normal
constructor, `train_flops(cfg, mix)` counts the model FLOPs of one rank's
training step from the shapes alone (recompute never counted). Nothing here
imports the program outside `build`."""
