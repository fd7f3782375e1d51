"""Published peaks of the devices the benchmark has run on, and the
arithmetic that turns shapes into operations and bytes.

A device that is not in the table is an error, not a default.
"""

from __future__ import annotations

from typing import Any, Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
# 819 GB/s, per chip. JAX reports the kind as "TPU v5 lite".
PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system architecture)",
    },
}


def peak(device_kind: str) -> Dict[str, Any]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"benchmark/peaks.py knows {sorted(PEAKS)}"
        ) from None


def gpt2_matmul_params(model: Dict[str, int]) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: the blocks' four projections and the tied LM head (at the
    published vocabulary; the position table and the norms do not)."""
    d, l, v = model["n_embd"], model["n_layer"], model["vocab_size"]
    return l * (4 * d * d + 2 * 4 * d * d) + v * d


def train_flops_per_token(model: Dict[str, int]) -> float:
    """6 N: forward and backward through the matrix multiplications.
    Attention's own products and anything recomputed are left out, so
    this is the conventional model-FLOPs figure, a little under the
    work the chip does."""
    return 6.0 * gpt2_matmul_params(model)


def mfu(tokens_per_s: float, model: Dict[str, int], chips: int,
        device_kind: str) -> float:
    """Share of the chips' bf16 peak, in percent."""
    return 100.0 * tokens_per_s * train_flops_per_token(model) / (
        chips * peak(device_kind)["flops_bf16"]
    )


def decode_step_mfu(step_s: float, step_bytes: float, device_kind: str) -> float:
    """The whole decode step's share of the chip's peak, in percent: the
    least time the step could take, which memory bandwidth sets (one token
    a row: ~2 FLOPs a weight byte), over the time it took.
    ``step_bytes`` is what the step has to read, by the family's own
    ``decode_step_bytes`` (``benchmark/families/<family>.py``)."""
    least = step_bytes / peak(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / step_s


def flash_fwd_cost(bh: int, t: int, d: int) -> Dict[str, float]:
    """Causal attention forward on [bh, t, d] bf16 operands: QK^T and PV
    over the lower triangle; reads q, k, v and writes o."""
    return {"flops": 2 * 2.0 * bh * t * t * d * 0.5,
            "bytes": 4 * 2.0 * bh * t * d}


def flash_bwd_cost(bh: int, t: int, d: int) -> Dict[str, float]:
    """Its backward: five products over the lower triangle (S again, dP,
    dV, dK, dQ); reads q, k, v, o, do and writes dq, dk, dv. A kernel
    pair that recomputes S and dP twice does seven; the two extra are
    not required work and are not counted."""
    return {"flops": 5 * 2.0 * bh * t * t * d * 0.5,
            "bytes": 8 * 2.0 * bh * t * d}


def roofline_share(cost: Dict[str, float], seconds: float,
                   device_kind: str) -> Dict[str, Any]:
    """Least time (the larger of operations over peak FLOP/s and bytes
    over peak bytes/s) over the time taken, in percent, and which of the
    two bounds it."""
    pk = peak(device_kind)
    by_flops = cost["flops"] / pk["flops_bf16"]
    by_bytes = cost["bytes"] / pk["hbm_bytes_per_s"]
    return {
        "share": 100.0 * max(by_flops, by_bytes) / seconds,
        "bound": "compute" if by_flops >= by_bytes else "bandwidth",
    }
