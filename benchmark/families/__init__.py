"""One module per model family: ``build`` maps the published sizes of a
configuration file onto the program's own model class, and the plain
reference (float32 ``jax.numpy``, no kernels, no cache) sits beside it."""
