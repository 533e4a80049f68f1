"""Device time of the Gated DeltaNet nodes per step, forward and backward:
`kda_ms`'s reader under this cell's name (that metric lists its cells, and
this PR may not edit the list). The nodes are the program's gated delta-rule
op with one decay a head, so their scopes are of kind `kda`
(`ff.kda.<name>`): the projections, the short convolution, the gates, the
chunks' operands, the chunk-to-chunk pass (and what the backward pass
recomputes of them) and the gated norm all count. Mean over chips. Absent
where the trace holds no such scope."""

from layer_metrics.kda_ms import LAYER, MOVES, SOURCE, UNIT, read  # noqa: F401
