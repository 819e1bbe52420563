"""Device milliseconds of the decode program (``_decode_tick``) per decode
tick, over the traced window."""
from readers import traced_delta


def read(rec):
    ticks = traced_delta(rec, "ticks")
    sec = rec.trace.data.module_seconds("_decode_tick")
    if ticks <= 0 or sec <= 0:
        return None
    return 1000.0 * sec / ticks
