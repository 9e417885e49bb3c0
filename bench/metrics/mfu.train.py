"""mfu.train: the whole step's share of the chips' peak, in percent:
train_tokens_per_s (real tokens) x model FLOPs per token (bench/flops.py:
6 x parameters + attention over the padded row, recompute not counted)
over chips x peak bf16 FLOP/s."""


def read(facts):
    if not facts["window_s"]:
        return None
    rate = facts["real_tokens"] / facts["window_s"]
    return 100.0 * rate * facts["flops_per_token"] / (
        facts["chips"] * facts["peak"]["flops"])
