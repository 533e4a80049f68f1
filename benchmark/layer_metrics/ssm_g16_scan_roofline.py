"""The selective scan's share of its roofline where a group is 16 heads wide:
`ssm_scan_roofline`'s reader under this cell's name (that metric lists its
cells, and this PR may not edit the list). The least time for
`kernel_costs()["ssm_scan"]` of the configuration (16 heads of 64 in one
group, forward and backward) over the device time under the scans' own
scopes (`ff.ssm.<name>/scan`), recomputation included. Absent where the
trace holds no such row, or the configuration states no such cost."""

from layer_metrics.ssm_scan_roofline import (  # noqa: F401
    LAYER, MOVES, SOURCE, UNIT, bound, read, scan_ms,
)
