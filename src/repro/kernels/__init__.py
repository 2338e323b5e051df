# Kernel layer: Pallas TPU kernels for the SAM hot path plus pure-jnp
# oracles (`ref.py`). `ops.py` is the only entry point the rest of the
# repo uses — it dispatches through the backend registry (`registry.py`,
# "ref" | "pallas" | "pallas-interpret", selectable per MemoryConfig or
# via REPRO_KERNEL_BACKEND; "pallas" by default on a TPU). See
# docs/kernels.md for every kernel's contract and how to add a backend.
