"""pad_share.train: pad slots over all slots of the batches the window
stepped, in percent (a count from the batches handed to the Trainer)."""


def read(facts):
    if not facts["slots"]:
        return None
    return 100.0 * (facts["slots"] - facts["real_tokens"]) / facts["slots"]
