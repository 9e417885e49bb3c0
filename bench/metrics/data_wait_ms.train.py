"""data_wait_ms.train: host milliseconds per window step that
``Trainer.step`` waited for its next device batch (the host pipeline and
its placement in ``_DevicePrefetch``): the mean of the step log's span
``train.data`` over the window's steps (``bench/scopes.py``). Nothing to
read from a program without a step log."""
import os
import sys

sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import scopes  # noqa: E402


def read(facts):
    found = scopes.window_records(facts)
    if found is None:
        return None
    _, records = found
    return 1000.0 * sum(r.spans.get("train.data", 0.0)
                        for r in records) / len(records)
