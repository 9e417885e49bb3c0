"""recompute_ms.train: device milliseconds per window step of the
operations the backward recomputes (pass ``remat``: under
``jax.checkpoint``, ``remat_policy="block"``), any scope.

Read by ``bench/scopes.py``: the trace's operations
(``facts["trace"]["op_s"]``) joined by HLO instruction name with the
scope maps of the programs the window's steps ran, from the program's
step log. Nothing to read from a program without one."""
import os
import sys

sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import scopes  # noqa: E402


def read(facts):
    return scopes.per_step_ms(facts, lambda scope, pas: pas == "remat")
