"""95th percentile over every request due in the window of (first prefill
chunk dispatched) - (due time), from the engine Tracer's `chunk` events
stamped on the benchmark's clock. A request with no chunk by the close
enters at its wait so far. Layer: engine and scheduler."""
import numpy as np


def read(ctx):
    if not ctx.queue_wait_ms:
        return None
    return float(np.percentile(ctx.queue_wait_ms, 95))
