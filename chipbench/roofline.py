"""The useful work of the serving kernels, and a kernel's share of its
roofline.

Work is counted for the real queries of a call, not for the query block
a kernel pads them to, so any later implementation of a stage is read
against the same work.  ``q`` queries, ``m`` corpus rows (users), ``i``
real items, ``k`` neighbours; float32 throughout.
"""
from __future__ import annotations

F32 = 4
# the serving programs (``kernels.ops`` fused pipelines), by module name
SERVING = r"_fused_recommend"


def stage_a(q: float, m: int, i: int) -> tuple:
    """(flops, bytes) of the neighbour search: every query against every
    corpus row, reading the corpus once and the queries once."""
    return 2 * q * m * i, F32 * (m * i + q * i)


def stage_b(q: float, k: int, i: int) -> tuple:
    """(flops, bytes) of the blend and top-n: each query's own row and
    its ``k`` neighbours' rows, read once, summed and scaled."""
    return 2 * q * (k + 1) * i, F32 * q * (k + 1) * i


def least_seconds(work: tuple, peaks: dict) -> float:
    """The least time the chip could take for ``work``: the larger of its
    flops at the float32 (``highest``) peak and its bytes at HBM speed."""
    flops, nbytes = work
    return max(flops / peaks["f32_highest_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def share(ctx, op_pattern: str, work) -> float | None:
    """Percent of its roofline that the kernel matching ``op_pattern``
    reached over the window's reads: the least time of every call's work
    over the kernel's device time.  ``work(q)`` is one call's work for
    ``q`` queries; a call's queries are the window's users served over
    the serving program's runs.  None where the trace holds no such
    kernel or no read."""
    if ctx.trace is None:
        return None
    secs = ctx.trace.op_seconds(op_pattern)
    _, calls = ctx.trace.program_seconds(SERVING)
    users = ctx.counters.get("read_users", 0)
    if secs <= 0 or not calls or not users:
        return None
    return 100.0 * calls * least_seconds(work(users / calls),
                                         ctx.peaks) / secs
