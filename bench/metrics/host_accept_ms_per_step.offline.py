"""Host milliseconds per traced ``ServeSession.step`` inside its
``serve.accept`` spans: token bookkeeping and finishing requests
(``program_trace.py``)."""
from program_trace import span_ms_per_step


def read(rec):
    return span_ms_per_step(rec, "serve.accept")
