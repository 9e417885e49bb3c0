"""data_host_ms.train: host milliseconds per step spent in the batch
iterator handed to the Trainer (MLMBatches: pad, corrupt), timed around
each ``next`` by the harness."""


def read(facts):
    if not facts["steps"]:
        return None
    return 1000.0 * facts["data_host_s"] / facts["steps"]
