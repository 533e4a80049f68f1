"""The selective scan's share of its roofline where a group goes as column
blocks: `ssm_scan_roofline`'s reader under this cell's name (that metric
lists its cells, and this PR may not edit the list). The least time for
`kernel_costs()["ssm_scan"]` of the configuration (64 heads of 64 in ONE
group at chunks of 256, nine scans forward and backward, B and C counted
once a position and not once a block) over the device time under the scans'
own scopes (`ff.ssm.<name>/scan`), recomputation, the blocks' re-reads of B
and C and the sum of dB's and dC's partials included: the numerator is the
least, so the share cannot pass 100. Absent where the trace holds no such
row, or the configuration states no such cost."""

from layer_metrics.ssm_scan_roofline import (  # noqa: F401
    LAYER, MOVES, SOURCE, UNIT, bound, read, scan_ms,
)
