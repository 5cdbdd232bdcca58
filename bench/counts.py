"""Operations and bytes of the masking kernels, computed from shapes.

``wirepath_bytes_moved`` is a copy of the arithmetic of
``repro.kernels.ops.wirepath_bytes_moved``, kept here so that the
kernels' roofline does not move when the program does.  The test
``bench/tests/test_bench_counts.py`` holds the two equal at the cells'
sizes.
"""

from __future__ import annotations

DEFAULT_REFINE_SWEEPS = 2


def wirepath_bytes_moved(n_params: int, gamma: float, *, fused: bool,
                         quantize: bool = True, wire: str = "coo",
                         refine_sweeps: int = DEFAULT_REFINE_SWEEPS,
                         assume_masked: bool = False) -> dict:
    """HBM bytes (reads + writes) to wire-encode one n-element delta:
    ``reads``, ``writes``, ``total``, ``payload_bytes`` and the per-stage
    ``breakdown``."""
    n = int(n_params)
    dense = 4 * n
    k = min(max(1, int(round(gamma * n))), n)
    vb = 1 if quantize else 4
    payload = (k * (4 + vb)) if wire == "coo" else (k * vb + (n + 7) // 8)
    if quantize:
        payload += 4
    breakdown = {}
    if not assume_masked:
        breakdown["select_reads"] = (1 + refine_sweeps) * dense
    elif fused and quantize:
        breakdown["select_reads"] = dense
    if fused:
        narrow = (n if quantize else dense) + (n + 7) // 8
        breakdown["encode_read"] = dense
        breakdown["encode_writes"] = narrow
        breakdown["compact_reads"] = narrow
        breakdown["payload_writes"] = payload
    else:
        breakdown["apply_read"] = dense
        breakdown["apply_write"] = dense
        breakdown["codec_rereads"] = 3 * dense
        breakdown["payload_writes"] = payload
    reads = sum(breakdown.get(s, 0) for s in (
        "select_reads", "encode_read", "compact_reads", "apply_read",
        "codec_rereads"))
    writes = sum(breakdown.get(s, 0) for s in (
        "encode_writes", "apply_write", "payload_writes"))
    return {"reads": reads, "writes": writes, "total": reads + writes,
            "payload_bytes": payload, "breakdown": breakdown}


def maskable_elements(leaf_sizes, min_leaf_size: int) -> int:
    """Elements of the leaves that the masking kernels sweep."""
    return sum(int(s) for s in leaf_sizes if int(s) >= min_leaf_size)


def mask_kernel_bytes(n: int, gamma: float, *, codec_fused: bool,
                      quantize: bool = False,
                      refine_sweeps: int = DEFAULT_REFINE_SWEEPS) -> int:
    """HBM bytes that the Pallas stages move for one client's delta of
    ``n`` maskable elements under kernel masking: the selection sweeps
    (histogram and ``refine_sweeps`` counts), the apply sweep's read and
    write, and, with the fused codec, its encode sweep's read and narrow
    writes.  The XLA packing and compaction around them are not counted.
    """
    m = wirepath_bytes_moved(n, gamma, fused=False, quantize=quantize,
                             refine_sweeps=refine_sweeps)["breakdown"]
    total = m["select_reads"] + m["apply_read"] + m["apply_write"]
    if codec_fused:
        e = wirepath_bytes_moved(n, gamma, fused=True, quantize=quantize,
                                 refine_sweeps=refine_sweeps,
                                 assume_masked=True)["breakdown"]
        total += e["encode_read"] + e["encode_writes"]
        if quantize:
            total += e["select_reads"]
    return total
