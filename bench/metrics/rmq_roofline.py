"""``kernels/rmq``: the batched range-minimum Pallas kernel's share (%) of
its roofline: the least time its bytes and operations allow on this chip
(``bench/kernels/rmq.py``, from each call's shapes, over
``bench/peaks.json``) over the kernel's time in the trace. Moves
``goodput_rps``."""
from bench.kernels import rmq
from bench.peaks import peaks


def read(run):
    t = run.trace
    calls = t.ops_matching(rmq.matches) if t is not None else []
    if not calls:
        return None
    peak = peaks(run.device_kind)
    least = sum(max(ops / peak["int_ops_per_s"],
                    nbytes / peak["hbm_bytes_per_s"])
                for ops, nbytes in map(rmq.cost, calls))
    return 100.0 * least / sum(op.dur_s for op in calls)
