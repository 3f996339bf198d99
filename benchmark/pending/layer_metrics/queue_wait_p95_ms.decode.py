"""Time a request waited for a slot: the 95th percentile of the
``queue_wait_ms`` argument of ``serve.request`` (``PendingRequest._resolve``
from the engine's stamp ``admitted``: ``submit()`` to the admission into a
slot), over the requests whose submission lies in the window (the span starts
at the submission).  Under independent arrivals this is what a burst costs
the callers behind it; in a closed loop it is the construction's."""

NAME = "queue_wait_p95_ms.decode"
UNIT = "ms"
LAYER = "decode engine"
MOVES = "request_p95_ms"


def read(facts):
    from benchmark import span_reduce
    return span_reduce.request_quantile(facts, "queue_wait_ms", 0.95)
