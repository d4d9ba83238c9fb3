"""The benchmark of tpu-rlr-fl: one cell, once, through `train.RoundEngine`.

`BENCHMARK.json` at the root of the repo names the cells; `README.md` here
says how a later PR adds one without editing a file that is there."""
