"""Micro-batcher: mean dispatched batch size over ``max_batch`` (%), for
the batches dispatched in the stretch the run reads
(``RuntimeTelemetry.batch_sizes``). Moves ``goodput_rps``."""
import numpy as np


def read(run):
    b0, b1 = run.batches
    sizes = run.sys_.rt.telemetry.batch_sizes[b0:b1]
    if not sizes:
        return None
    return 100.0 * float(np.mean(sizes)) / run.sys_.max_batch
