"""Bytes the fused STORM partial step must move in one train step.

``storm3_step_flat(p, m, g_old, lrs, decays)`` reads the parameters p (their
own dtype), the float32 momentum m and the float32 old-iterate oracle
g_old, and writes p and m: per element two parameter widths and three
float32 words.  Every section of the algorithm's flat state (x the body;
y, and u where there is one, the head) of every client takes one pass: a
launch gated by participation still streams every client's rows.  The
per-tile (lr, decay) tables are a few words per 64 Ki elements and are left
out.
"""
from __future__ import annotations

import numpy as np

#: the model's parameters each section of the flat state holds
PARAMS = {"x": "body", "y": "head", "u": "head"}


def bytes_per_step(params, clients: int, sections) -> int:
    """``params``: the model's {"body", "head"} parameters (arrays or shape
    structs); ``sections``: the algorithm's section names."""
    import jax
    total = 0
    for section in sections:
        for leaf in jax.tree.leaves(params[PARAMS[section]]):
            size = int(np.prod(leaf.shape, dtype=np.int64))
            total += size * (2 * np.dtype(leaf.dtype).itemsize + 3 * 4)
    return clients * total
