"""Required FLOPs of one forward pass per token, one module per model
family (``flops_per_token(sizes, seq_len)``).  They count the matrix
products of the model and the score/value or state-space chunk products
that ``2 * parameters`` omits, at the causal half where a mask makes half
of a square product zero.  They leave out the embedding gather, norms,
elementwise work, recomputation and any expert the router did not choose.

An algorithm whose oracle requires more than the traffic's
``forward_units`` forward passes counts the rest per token in a module of
its own name (``flops_per_token(sizes, schedule)``)."""
