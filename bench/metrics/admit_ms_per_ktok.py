"""Device milliseconds of the session's admit program
(``_admit_fused_paged``: a batch of prompts prefilled into the paged pool and
their first tokens sampled) per 1000 prompt tokens it admitted, over the
traced window (``program_trace.py``). The tokens are the session's
``prefill_tokens``, each prompt at its own bucket, so the reading follows the
program's speed rather than which prompt sizes fall in the window."""
from program_trace import of
from readers import traced_delta

PROGRAM = "_admit_fused_paged"


def read(rec):
    pt = of(rec)
    if pt is None or not pt.calls(PROGRAM):
        return None
    tokens = traced_delta(rec, "prefill_tokens")
    if tokens <= 0:
        return None
    return 1e6 * pt.data.module_seconds(PROGRAM) / tokens
