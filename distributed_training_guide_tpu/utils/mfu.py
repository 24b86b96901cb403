"""Model-FLOPs-utilization accounting.

The reference only reports tokens/s (``01-single-gpu/train_llm.py:166``); the
TPU build's north-star metric is MFU, so we add the standard accounting:
``6 * n_params`` matmul FLOPs per token for fwd+bwd, plus the attention
quadratic term ``12 * n_layers * hidden * seq`` (fwd+bwd, causal halves the
scores but flash kernels still compute block-wise — we use the conventional
dense count so numbers are comparable with published MFU figures).
"""
from __future__ import annotations

import jax


def transformer_flops_per_token(
    n_params: int,
    n_layers: int,
    hidden_size: int,
    seq_len: int,
    include_embedding: bool = False,
    vocab_size: int = 0,
    attn_kv_len: float | None = None,
) -> float:
    """Training FLOPs (fwd+bwd) per token.

    ``attn_kv_len``: mean keys each query actually attends (defaults to
    ``seq_len``, the conventional dense-causal count). Banded attention
    (sliding windows, per-layer schedules) computes O(S*window), not
    O(S^2) — pass ``banded_attention_kv_length(cfg, seq_len)`` for the
    honest roofline; published-MFU comparisons keep the dense default."""
    params = n_params
    if not include_embedding and vocab_size:
        params = n_params - vocab_size * hidden_size
    matmul = 6.0 * params
    attention = 12.0 * n_layers * hidden_size * (
        seq_len if attn_kv_len is None else attn_kv_len)
    return matmul + attention


def banded_attention_kv_length(cfg, seq_len: int) -> float:
    """Mean effective kv context per query across layers under the config's
    window schedule — ``min(seq, window)`` per layer, averaged over a
    per-layer pattern (``layer_windows``, 0 = full attention that layer) or
    taken from the uniform ``sliding_window``; ``seq_len`` when unwindowed.
    This is the O(S*window) attention cost the banded flash kernel (and the
    matching xla mask's useful work) actually pays once S >> window."""
    lw = getattr(cfg, "layer_windows", None)
    if lw:
        return sum(min(seq_len, w) if w else seq_len for w in lw) / len(lw)
    w = getattr(cfg, "sliding_window", None)
    if w:
        return float(min(seq_len, w))
    return float(seq_len)


# One table of per-chip peaks, keyed by the ``device_kind`` JAX reports
# (lower-cased, without the "TPU " prefix) plus the short names people type
# for ``--preflight-target``. Values: (bf16 dense FLOP/s, ICI bytes/s egress
# over all links in one direction, source). A kind that is not here —
# "cpu" included — is an error, never a default: a utilization against an
# invented peak is not a measurement.
_CLOUD_DOCS = "Google Cloud TPU documentation, system architecture, "
CHIP_PEAKS: dict[str, tuple[float, float, str]] = {
    "v5 lite": (197e12, 1600e9 / 8, _CLOUD_DOCS + "TPU v5e"),
    "v5e": (197e12, 1600e9 / 8, _CLOUD_DOCS + "TPU v5e"),
    "v5litepod": (197e12, 1600e9 / 8, _CLOUD_DOCS + "TPU v5e"),
    "v5p": (459e12, 4800e9 / 8, _CLOUD_DOCS + "TPU v5p"),
    "v5": (459e12, 4800e9 / 8, _CLOUD_DOCS + "TPU v5p"),
    "v6 lite": (918e12, 3584e9 / 8, _CLOUD_DOCS + "TPU v6e"),
    "v6e": (918e12, 3584e9 / 8, _CLOUD_DOCS + "TPU v6e"),
    "v4": (275e12, 2400e9 / 8, _CLOUD_DOCS + "TPU v4"),
    "v3": (123e12, 1400e9 / 8, _CLOUD_DOCS + "TPU v3"),
}


def chip_peaks(device: "jax.Device | None" = None,
               device_kind: str | None = None) -> tuple[float, float, str]:
    """``(bf16 FLOP/s, ICI bytes/s, source)`` of one chip. ``device_kind``
    names a TARGET chip (e.g. "v5p") without probing a local device — the
    preflight roofline prices pod plans from CPU hosts. Raises
    ``ValueError`` for a kind the table does not have."""
    if device_kind is None:
        device = device or jax.local_devices()[0]
        device_kind = getattr(device, "device_kind", "") or device.platform
    key = device_kind.lower().removeprefix("tpu").strip()
    if key not in CHIP_PEAKS:
        raise ValueError(
            f"no peak FLOP/s or ICI bandwidth on record for device kind "
            f"{device_kind!r}; known kinds: {sorted(CHIP_PEAKS)} — add it "
            f"to utils/mfu.py CHIP_PEAKS with its source")
    return CHIP_PEAKS[key]


def device_peak_flops(device: "jax.Device | None" = None,
                      device_kind: str | None = None) -> float:
    return chip_peaks(device, device_kind)[0]


def compute_mfu(tokens_per_s: float, flops_per_token: float, n_chips: int = 1,
                peak_flops_per_chip: float | None = None) -> float:
    peak = peak_flops_per_chip or device_peak_flops()
    return (tokens_per_s * flops_per_token) / (peak * n_chips)


def device_ici_bandwidth(device: "jax.Device | None" = None,
                         device_kind: str | None = None) -> float:
    """Bytes/s of ICI egress per chip. The preflight roofline
    (train/preflight.py) divides ring-collective bytes by this, the standard
    first-order model; real meshes split it over links/axes, so treat
    results as a best-case bound, not a simulator."""
    return chip_peaks(device, device_kind)[1]
