"""Published peaks by ``device_kind`` and the operation and byte counts
the roofline shares are taken against. A device that is not in the table
is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect per chip
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30, "ici_bytes_per_s": 200e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def train_flops_per_token(n_matmul_params: int, n_layer: int, d_model: int,
                          seq: int, causal: bool) -> float:
    """Operations the forward and backward passes need per token: 6 per
    parameter that sits in a matrix multiplication (the embedding gather
    does none), plus attention's two matmuls, 12*L*D*S forward and
    backward over a whole sequence, halved under a causal mask.
    Recomputed operations do not count."""
    attn = 12.0 * n_layer * d_model * seq
    return 6.0 * n_matmul_params + (attn / 2 if causal else attn)


def flash_call_flops(batch: int, heads: int, seq: int, head_dim: int,
                     causal: bool, backward: bool) -> float:
    """One flash-attention call: QK^T and PV forward (4*B*H*S*S*Dh); the
    backward needs dV, dP, dQ, dK plus the recomputed scores: five
    matmuls (10*B*H*S*S*Dh). Causal halves the needed work."""
    per = (10.0 if backward else 4.0) * batch * heads * seq * seq * head_dim
    return per / 2 if causal else per


def flash_call_bytes(batch: int, heads: int, seq: int, head_dim: int,
                     itemsize: int, backward: bool) -> float:
    """Least HBM traffic of one call: forward reads q, k, v and writes o
    (+ logsumexp in f32); backward reads q, k, v, o, do (+ lse) and writes
    dq, dk, dv."""
    t = batch * heads * seq * head_dim * itemsize
    lse = batch * heads * seq * 4
    return (8 * t + lse) if backward else (4 * t + lse)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> dict:
    """Least time the chip could take over the time taken, and which of
    the two limits binds."""
    t_compute = flops / peaks["flops_per_s"]
    t_memory = nbytes / peaks["bytes_per_s"]
    return {"share_pct": 100.0 * max(t_compute, t_memory) / seconds,
            "bound": "compute" if t_compute >= t_memory else "memory"}
