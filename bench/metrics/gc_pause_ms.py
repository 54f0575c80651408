"""Host interpreter: the longest garbage-collector pause (ms) that started
in the stretch read, from the program's process-wide collector clock
(``repro.obs.tracing.gc_pauses``); 0 when none ran. Moves
``goodput_rps``."""
from bench import stages


def read(run):
    try:
        from repro.obs.tracing import gc_pauses
    except ImportError:
        return None
    return max([(t1 - t0) / 1e6 for _, t0, t1 in gc_pauses()
                if stages.in_stretch(run, t0)], default=0.0)
