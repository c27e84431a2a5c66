"""FedBiOAcc-Local's oracle beyond the traffic's ``forward_units``.

At each of the two STORM points a client's oracle takes the gradient of f
(3 forward units), one linearization of the gradient of g that gives
d_y g and d2_xy g . ihvp (5 units), as FedBiOAcc's does, and the truncated
Neumann series ihvp = tau sum_{k=0}^{Q} (I - tau d2_yy g)^k d_y f: Q products
with d2_yy g.  y is the head, so once the features h exist each such
product is two head matrix products per token, the tangent logits h . v and
the tangent of the head's gradient h^T . delta, each 2 d_model vocab FLOPs
(every family here ends in one untied [d_model, vocab] head).  Counted per
token of a participant."""


def flops_per_token(s: dict, hp: dict) -> float:
    return 2 * hp["neumann_q"] * 2 * (2 * s["d_model"] * s["vocab_size"])
