"""Host milliseconds per traced ``ServeSession.step`` inside its
``serve.admit`` span and no span nested in it: admission decisions and the
admit dispatches (``program_trace.py``)."""
from program_trace import span_ms_per_step


def read(rec):
    return span_ms_per_step(rec, "serve.admit")
