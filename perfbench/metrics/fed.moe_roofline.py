"""Percent of their roofline time that the held experts' grouped matmuls
(the ``ragged-dot`` operations) reach in the traced rounds: the least time
their required work takes at the chip's peaks, the forward pass and the
input gradient of each layer's step at the routed pair counts
(``flops_hybrid.expert_gmm_cost``), over their device time. The device
time includes any recomputation the program does; the required work
does not."""


def read(ctx):
    t, t_min = (ctx.counters.get("gmm_s_per_round"),
                ctx.counters.get("gmm_roofline_s_per_round"))
    return 100.0 * t_min / t if t and t_min else None
