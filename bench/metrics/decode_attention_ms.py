"""Device milliseconds per decode tick of the decode program's
(``_decode_tick``) operations whose innermost program scope is
``attention``, over the traced window (``program_trace.py``)."""
from program_trace import decode_scope_ms


def read(rec):
    return decode_scope_ms(rec, "attention")
