"""The Gated DeltaNet recurrence's share of its roofline: the least time the
chip could take for the recurrence of every such layer in the SCALAR-decay
form, forward and backward (the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s; `kernel_costs()["gdn_scan"]` of the configuration: K K^T
and Q K^T once a KEY head, the triangular system solved once, the state's
products a value head, and q, k, v, the log-decay, beta and o once a pass)
over the device time of the operations under the recurrence's two parts
inside the node's scope (`ff.kda.<name>/scan`, the chunk-to-chunk pass, and
`ff.kda.<name>/prep`, the chunks' operands), recomputation included:
`kda_scan_roofline`'s arithmetic on another least. A form that broadcasts
the head's decay over its key channels computes and moves more than this
least counts, and pays for it in the share. Absent where the trace holds no
such row, or the configuration states no such cost."""

from layer_metrics.kda_scan_roofline import bound, scan_ms

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(ctx):
    ms = scan_ms(ctx)
    least = bound(ctx, "gdn_scan") if ms else None
    if not least:
        return None
    return 100.0 * least[1] * 1e3 / ms
