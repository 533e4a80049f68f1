"""Device time per step of state-space nodes whose ONE group is 64 heads wide
(4,096 columns: more than a program of the scan kernels holds, so the group
goes as column blocks), forward and backward: `ssm_ms`'s reader under this
cell's name (that metric lists its cells, and this PR may not edit the
list). Every operation under a scope of kind `ssm` counts: the two
projections, the short convolution, the scan and the gated norm. Absent
where the trace holds no such scope."""

from layer_metrics.ssm_ms import LAYER, MOVES, SOURCE, UNIT, read  # noqa: F401
