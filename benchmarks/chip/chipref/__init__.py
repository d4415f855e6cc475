"""Plain references of what the benchmark's cells train.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
one module per model family (``lstm``, ``transformer``) with the forward
pass, the loss, the weight initialisation from a seed and the FLOPs a
token needs, and ``rgc`` for the residual gradient compression optimizer
the cells run. Nothing here imports the program under test; the weights
are made here from the seed and handed to the program and the reference
alike.
"""
