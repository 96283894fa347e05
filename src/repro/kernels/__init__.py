"""Pallas TPU kernels for the framework's compute hot spots, each with
ops.py (host dispatch) and ref.py (numpy / pure-jnp oracle):

- flash_attention/  block-tiled online-softmax attention
                    (GQA, causal, sliding window, decode offsets)
- ssd/              Mamba2 SSD chunked scan with VMEM state carry
- conflict_matrix/  tiled construction of the paper's dense conflict
                    rules (TPU-offload form of core/conflict.py)
- sbts_step/        the device SBTS engine's all-pairs popcount
                    (|N(v) ∩ S_k| for every trajectory and vertex)

On a TPU they run compiled; on the CPU backend (the test suite) they
run in Pallas interpret mode.  `interpret_mode` is the one place that
choice is made from the platform.
"""


def interpret_mode() -> bool:
    """True when JAX's default backend is the CPU, where Pallas kernels
    can only run in interpret mode; False on an accelerator."""
    import jax
    return jax.default_backend() == "cpu"
