"""Engine syncs: mean per dispatch (ms) of the ``engine.*.sync`` stages,
where the host blocks on the device and reads its answers back. Moves
``goodput_rps``."""
from bench import stages


def read(run):
    return stages.per_dispatch_ms(
        run, lambda name: name.startswith("engine.")
        and name.endswith(".sync"))
