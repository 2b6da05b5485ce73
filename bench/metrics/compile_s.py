"""compile_s: seconds JAX spent tracing, lowering, compiling or loading
from the persistent cache during set-up, from its monitoring events
(the harness's CompileClock).  Moves setup_s."""


def read(rec):
    return rec.get("compile_s")
