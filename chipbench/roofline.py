"""Peak table and the operations and bytes each kernel call needs.

Peaks are keyed by ``device_kind`` as JAX reports it; a device that is not
in the table is an error, never a default. The counts are what the
algorithm needs for a call at its shapes, not what an implementation
happens to issue: a kernel that does extra work reads as a lower share.
"""
from __future__ import annotations

import math

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture): per chip
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
    "TPU v5 lite": {"ops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

F32 = 4


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}") from None


def chunks(bucket: int, query_chunk: int) -> list[int]:
    """Rows per pipeline call for one micro-batch of ``bucket`` rows: the
    batch is cut into calls of ``query_chunk`` rows, the last padded."""
    per = min(query_chunk, bucket)
    return [per] * math.ceil(bucket / per)


def cells_per_chip(config: dict, chips: int) -> int:
    dep = config["deployment"]
    return dep["nu"] * dep["p"] // chips


def compact_width(prm: dict, n_loc: int, l_loc: int) -> int:
    """Candidates kept per query and cell: the gather width, capped by
    ``c_comp`` and by the cell's points rounded up to 128 lanes."""
    slot = max(prm["c_max"], prm["L_in"] * prm["c_in"])
    c_total = l_loc * slot
    cc = c_total if prm["c_comp"] <= 0 else min(prm["c_comp"], c_total)
    return max(1, min(cc, -(-n_loc // 128) * 128))


def hash_pack_calls(config: dict, bucket: int, chips: int) -> list[tuple[float, float]]:
    """(ops, bytes) of each hash kernel call one chip makes for a batch:
    per cell and row chunk, the outer bit-sampling signatures of the cell's
    tables (one compare per bit) and the inner sign projections (a
    multiply-add per weight); on a routed deployment also the router's one
    call over the whole batch and every outer table."""
    prm, d = config["slsh"], config["data"]["d"]
    dep = config["deployment"]
    w_out, w_in = -(-prm["m_out"] // 32), -(-prm["m_in"] // 32)

    def outer(q, tables):
        return (q * tables * prm["m_out"],
                F32 * (q * d + 2 * tables * prm["m_out"] + q * tables * w_out))

    inner = lambda q: (2 * q * d * prm["L_in"] * prm["m_in"],  # noqa: E731
                       F32 * (q * d + d * prm["L_in"] * prm["m_in"] + q * prm["L_in"] * w_in))
    per_cell = []
    for q in chunks(bucket, prm["query_chunk"]):
        per_cell += [outer(q, prm["L_out"] // dep["p"]), inner(q)]
    router = [outer(bucket, prm["L_out"])] if dep.get("routed") else []
    return per_cell * cells_per_chip(config, chips) + router


def l1_topk_calls(config: dict, bucket: int, chips: int) -> list[tuple[float, float]]:
    """(ops, bytes) of each distance/top-k kernel call one chip makes for a
    batch: per cell and row chunk, |q - x| summed over d for every kept
    candidate (three ops per coordinate), reading each candidate row and
    its mask once and writing k (distance, position) pairs per row."""
    prm, d = config["slsh"], config["data"]["d"]
    dep = config["deployment"]
    n_loc = config["data"]["n_points"] // dep["nu"]
    c = compact_width(prm, n_loc, prm["L_out"] // dep["p"])
    out = []
    for q in chunks(bucket, prm["query_chunk"]):
        out.append((3 * q * c * d, F32 * q * c * d + q * c + F32 * q * d + 8 * q * prm["k"]))
    return out * cells_per_chip(config, chips)


CALLS = {"hash_pack": hash_pack_calls, "l1_topk": l1_topk_calls}


def least_time(calls: list[tuple[float, float]], device_kind: str) -> tuple[float, str]:
    """The least time the chip needs for ``calls``: per call the larger of
    ops over peak and bytes over HBM bandwidth, summed; and which bound
    held for most of that time (``"compute"`` or ``"memory"``)."""
    pk = peaks(device_kind)
    t_ops = t_mem = 0.0
    for ops, nbytes in calls:
        a, b = ops / pk["ops_per_s"], nbytes / pk["hbm_bytes_per_s"]
        if a >= b:
            t_ops += a
        else:
            t_mem += b
    return t_ops + t_mem, "compute" if t_ops > t_mem else "memory"
