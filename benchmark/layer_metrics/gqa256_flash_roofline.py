"""The output-gated grouped-query attention kernels' share of their roofline
at heads of 256 over 8,192 causal positions: `gqa64_flash_roofline`'s reader
under this cell's name (that metric lists its cells, and this PR may not
edit the list). The least time the chip could take for
`kernel_costs()["flash"]` of the configuration (the causal half of the pairs
at the TRUE 16 query heads and d = 256; q and o at 16 heads, k and v at the
2 published key/value heads) over the device time of the Pallas calls under
the attention nodes' scopes (`ff.ring_attention.<name>`, forward and
backward with its delta kernel). The norm-and-rotary pass and the gate are
the node's time and not the kernels' (`attention_ms` has them, and the
node's parts `rows` and `gate` tell them apart in `step_anatomy`'s table).
The route each attention node took goes to standard error beside the share.
Absent where the trace holds no such Pallas call, or the configuration
states no such cost."""

from layer_metrics.gqa64_flash_roofline import (  # noqa: F401
    LAYER, MOVES, SOURCE, UNIT, read,
)
