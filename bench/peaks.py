"""Published per-chip peaks, keyed by ``device_kind`` (``peaks.json``).

The only integer peak Google publishes for a v5e is the matrix unit's
int8 rate; the QAC kernels run on the vector unit, so that peak overstates
what they could reach by operations, and their least time is set by bytes.
A device that is not in the table is an error, never a default."""
import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{TABLE.name} (have {sorted(table)})")
    return table[device_kind]
