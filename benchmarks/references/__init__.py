"""Plain references, one file an architecture, found by the name a
configuration file gives under ``"reference"``. Each has one function,

    forward(params, model, ids, positions) -> float32 (len(positions), V)

the architecture's whole causal forward over ``ids`` (1, T) in one pass,
logits at the asked positions only: straightforward ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")``, no cache, no
kernel, no batching, no capacity. ``params`` is the served parameter
tree as it is stored on the chip, read a layer at a time and upcast
inside the reference's own loop (bf16 to float32; int8 times its float32
scale an output channel); ``model`` is the configuration file's
``model`` group as a dict. Nothing here imports the package under test
(a test greps), and no file imports another.
"""
