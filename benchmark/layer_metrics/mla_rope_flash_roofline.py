"""The latent-attention kernels' share of their roofline at 8,192 causal
positions, six nodes with a rotary on the shared slice: `mla_flash_roofline`'s
reader under this cell's name (that metric lists its cells, and this PR may
not edit the list). The least time the chip could take for
`kernel_costs()["flash"]` of the configuration (the causal half of the pairs
at the TRUE 192-wide key, not the 256 the kernel pads it to, over every
latent node, the module's counted) over the device time of the Pallas calls
under the attention nodes' scopes (`ff.ring_attention.<name>`, forward and
backward with its delta kernel). The rotary pass, the projections and the
key's assembly are the node's time and not the kernels' (`mla_rope_ms` has
them by part). Absent where the trace holds no such Pallas call, or the
configuration states no such cost."""

from layer_metrics.mla_flash_roofline import (  # noqa: F401
    LAYER, MOVES, SOURCE, UNIT, read,
)
