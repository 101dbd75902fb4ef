"""decision_p95_ms.served: the 95th percentile of one decision's
wall time on the served loop (span ``bench.decision``)."""

import numpy as np


def read(ctx):
    d = ctx.spans.get("bench.decision")
    return 1e3 * float(np.percentile(d, 95)) if d else None
