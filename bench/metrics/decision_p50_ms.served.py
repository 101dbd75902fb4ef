"""decision_p50_ms.served: the 50th percentile of one decision's
wall time on the served loop (span ``bench.decision``)."""

import numpy as np


def read(ctx):
    d = ctx.spans.get("bench.decision")
    return 1e3 * float(np.percentile(d, 50)) if d else None
