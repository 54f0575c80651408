"""A tiny cell, defined by data files alone under a temporary root."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "name": "tiny",
    "source": "test",
    "k": 10,
    "dataset_seed": 5,
    "generator": {"n_queries": 4000, "vocab_size": 700, "zipf_s": 1.07,
                  "mean_terms": 2.6, "mean_term_chars": 5.0,
                  "max_terms": 7},
    "parse": {"max_terms": 8, "max_term_chars": 24},
    "serving": {"max_batch": 16, "slack_us": 20000.0,
                "cache_entries": 4096, "session_entries": 4096,
                "specialize_list_pad": False},
    "reference": "bench/reference.py",
    "reduced": [],
}
TINY_TRAFFIC = {"request_rate": 200.0, "mean_keystroke_ms": 60.0,
                "p_backspace": 0.06, "max_backspace": 3,
                "popularity_zipf_s": 1.05, "first_term": False,
                "warm_seconds": 1.0, "trace_seconds": 1.0}


def make_root(tmp: Path, traffic: dict = TINY_TRAFFIC,
              name: str = "tiny.sessions") -> Path:
    """A root holding BENCHMARK.json with one more cell, its configuration
    and its traffic mix: new data files, and no code (the plain reference
    is copied beside the configuration, as a configuration's is)."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": name, "config": "tiny",
                              "traffic": "tiny", "chips": 1, "why": "test"})
    for m in spec["per_layer"]:
        m.get("workloads", []).append(name)
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY_CONFIG))
    (tmp / "bench" / "traffic" / "tiny.json").write_text(json.dumps(traffic))
    shutil.copy(ROOT / "bench" / "reference.py", tmp / "bench")
    return tmp
