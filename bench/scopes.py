"""What the scope readers (``bench/metrics/*_ms.train.py``) share: the
program's step log for the window's steps, and the join of the trace's
device operations with the program's named scopes and passes.

The step log is the program's (``repro.obs.STEP_LOG``): a record per
``Trainer.step`` with its host spans and the key of the compiled program
it ran, whose map from HLO instruction to ``(scope, pass)`` the log
reads from the compiled HLO's ``op_name`` metadata (``repro.obs``'s
``scope_map``). A program without it gives the readers nothing to read.
"""


def window_records(facts):
    """``(log, records)``: the step log and its records of the window's
    ``facts["steps"]`` steps; ``None`` where the program keeps no step log
    or it holds fewer records than the window stepped. ``repro`` is
    imported here, at read time: ``bench/run.py`` loads the readers before
    ``src`` is on the path."""
    try:
        from repro.obs import STEP_LOG
    except ImportError:
        return None
    n = facts["steps"]
    records = STEP_LOG.last("train", n)
    return (STEP_LOG, records) if n and len(records) == n else None


def per_step_ms(facts, keep):
    """Device milliseconds per window step of the trace's operations whose
    ``(scope, pass)`` satisfies ``keep(scope, pass)``; ``None`` where the
    step log names no scope among the window's operations."""
    found = window_records(facts)
    if found is None:
        return None
    log, records = found
    by = log.device_seconds(facts["trace"]["op_s"], records)
    if not any(scope for scope, _ in by):
        return None
    return 1000.0 * sum(s for (scope, pas), s in by.items()
                        if keep(scope, pas)) / len(records)
