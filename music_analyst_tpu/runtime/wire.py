"""H2D wire-format policy: narrow payloads, count bytes, donate buffers.

Every batch crosses host→device once, so the policy ships the fewest
bytes that lose nothing (what the narrowing buys end to end: not
measured on this host).  The policy, mirroring ``_wire_dtype`` in
``models/distilbert.py``:

* **token ids** — int16 when the vocab fits 2¹⁵ (BERT's 30522 does,
  llama's 128256 does not);
* **lengths / segment starts / row lengths / bucket indices** — int16
  whenever the max representable position fits 2¹⁵
  (:func:`narrow_lengths`), widened back to int32 on device inside the
  jitted program;
* **boolean masks** — 8 mask bits per byte (:func:`pack_mask` /
  :func:`unpack_mask`).  The audit of current H2D payloads found **no**
  host-shipped mask arrays — every engine derives masks on device from
  lengths/segment ids, which is strictly cheaper — so these helpers
  exist for future payloads (and are contract-tested), not retrofits.

Every transfer site reports ``pipeline.h2d_bytes`` (what actually
shipped) and ``pipeline.h2d_bytes_saved`` (vs. the int32/bool baseline)
via :func:`count_h2d_bytes`, so the savings are a measured number in the
run manifest, not a comment.

:func:`forward_donation_kwargs` centralizes the ``donate_argnums``
policy for steady-state jitted forwards: on real accelerators donating
the input batch lets XLA reuse its H2D staging buffer for temporaries
instead of holding it live across the step; the CPU-emulated test mesh
gets no donation for pure data args (no matching output buffer to alias
— XLA would just warn "donated buffers were not usable" on every
compile).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import numpy as np

from music_analyst_tpu.telemetry import get_telemetry

_INT16_MAX = 1 << 15


def narrow_lengths(values: np.ndarray, max_value: int) -> np.ndarray:
    """Cast an integer payload to int16 when every representable value
    (``0..max_value``) fits, else int32.  Lossless by construction —
    callers widen with ``.astype(jnp.int32)`` on device."""
    dtype = np.int16 if max_value < _INT16_MAX else np.int32
    return np.asarray(values, dtype=dtype)


def pack_mask(mask: np.ndarray) -> np.ndarray:
    """Pack a boolean mask's last axis to 8 bits per byte (uint8).

    ``[..., S]`` bool → ``[..., ceil(S/8)]`` uint8, big-endian within the
    byte (numpy's ``packbits`` default, matched by :func:`unpack_mask`).
    """
    mask = np.asarray(mask, dtype=bool)
    return np.packbits(mask, axis=-1)


def unpack_mask(packed, length: int):
    """Device-side inverse of :func:`pack_mask` (jnp has no unpackbits).

    ``[..., nbytes]`` uint8 → ``[..., length]`` bool, traceable inside a
    jitted program so the widened mask never crosses the wire.
    """
    import jax.numpy as jnp

    packed = jnp.asarray(packed, dtype=jnp.uint8)
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)  # bit 7 first
    bits = (packed[..., None] >> shifts) & jnp.uint8(1)   # [..., nbytes, 8]
    flat = bits.reshape(*packed.shape[:-1], packed.shape[-1] * 8)
    return flat[..., :length].astype(bool)


def count_h2d_bytes(
    arrays: Sequence[Any],
    baseline_bytes: Optional[int] = None,
    prefix: str = "pipeline",
) -> int:
    """Count one transfer's payload bytes into the run's telemetry.

    ``<prefix>.h2d_bytes`` accumulates what actually shipped;
    ``<prefix>.h2d_bytes_saved`` accumulates the reduction against
    ``baseline_bytes`` — by default the 4-bytes-per-element wire every
    payload used before narrowing.  Returns the shipped byte count.
    """
    shipped = sum(int(a.nbytes) for a in arrays)
    if baseline_bytes is None:
        baseline_bytes = sum(int(a.size) * 4 for a in arrays)
    tel = get_telemetry()
    tel.count(f"{prefix}.h2d_bytes", shipped)
    saved = int(baseline_bytes) - shipped
    if saved > 0:
        tel.count(f"{prefix}.h2d_bytes_saved", saved)
    return shipped


def forward_donation_kwargs(*argnums: int) -> Dict[str, Any]:
    """``jit`` kwargs donating the given input-batch argnums — on real
    accelerators only.

    Donating the steady-state forward's data args frees each batch's H2D
    staging buffer at program start (the runtime may reuse the space for
    temporaries) instead of pinning it for the whole step.  On the CPU
    test backend a data arg has no same-shape output to alias, so XLA
    ignores the donation and warns on every compile — skip it there.
    Train-step *state* donation is different (state-in aliases state-out
    exactly) and stays unconditional in ``engines/train.py``.
    """
    import jax

    if jax.default_backend() == "cpu":
        return {}
    return {"donate_argnums": argnums}
