"""Network-entry dtype policy shared by the model classes.

The ETL tier ships uint8 image batches over the host->device link (4x
fewer bytes than float32 — a quarter of the DMA traffic); the cast to
the compute dtype happens HERE, inside the jitted step, so the wire
carries bytes and the MXU sees bf16/f32.  Reference role: the
ImageRecordReader -> normalizer -> fit() pipeline (SURVEY.md §2.2
DataVec ETL), which moves float buffers; shipping uint8 is the
TPU-native improvement.
"""

from __future__ import annotations

import jax.numpy as jnp


def entry_cast(x, bf16: bool):
    """Cast a network input to the compute dtype.

    - float inputs follow the bf16 compute flag (unchanged behavior);
    - uint8 inputs are IMAGE bytes: cast to the compute dtype on device,
      value-preserving (0..255 stays 0..255 — normalizers have already
      been applied host-side in integer space or run as graph ops);
    - wider integer inputs (int32/int64 token ids for embedding layers)
      pass through untouched.
    """
    if jnp.issubdtype(x.dtype, jnp.floating):
        return x.astype(jnp.bfloat16) if bf16 else x
    if x.dtype == jnp.uint8:
        return x.astype(jnp.bfloat16 if bf16 else jnp.float32)
    return x
