"""Device milliseconds per decode tick of the decode program's
(``_decode_tick``) operations in no program scope: what XLA adds, such as
the layer scan's copies of the KV pool (``program_trace.py``)."""
from program_trace import decode_scope_ms


def read(rec):
    return decode_scope_ms(rec, None)
