"""Host prep of a dispatch: mean per dispatch (ms) of the runtime's
``runtime.batch`` (queue pop, input stacking), the frontend's
``frontend.route`` (class routing, bucket padding, list-pad bound) and the
engines' ``engine.*.launch`` (the jitted call until it returns) stages.
Moves ``goodput_rps``."""
from bench import stages


def _prep(name: str) -> bool:
    return name in ("runtime.batch", "frontend.route") or (
        name.startswith("engine.") and name.endswith(".launch"))


def read(run):
    return stages.per_dispatch_ms(run, _prep)
