"""Time to the first token: the 95th percentile of the ``ttft_ms`` argument
of ``serve.request`` (``submit()`` to the token the admission's prefill
chose, the engine's stamp ``first_token``), over the requests whose
submission lies in the window.  It is the queue wait plus the ticks the
admission shared with others plus one prefill."""

NAME = "ttft_p95_ms.decode"
UNIT = "ms"
LAYER = "decode engine"
MOVES = "request_p95_ms"


def read(facts):
    from benchmark import span_reduce
    return span_reduce.request_quantile(facts, "ttft_ms", 0.95)
