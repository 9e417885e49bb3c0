"""Continuous-batching engine: outputs equal isolated (batch-1) greedy
decoding for every request, regardless of admission interleaving."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import ModelConfig
from repro.models.model import build_model
from repro.obs import MetricsRegistry, TraceRecorder
from repro.serving.engine import Engine, EngineOverloaded, Request


def build(family="dense", **over):
    kw = dict(
        name="t", family=family, num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=64, dtype="float32",
    )
    if family == "ssm":
        kw.update(d_ff=0, num_kv_heads=4, ssm_state=16, ssm_headdim=32, ssm_chunk=8)
    kw.update(over)
    cfg = ModelConfig(**kw)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def isolated_greedy(model, params, prompt, n, max_len=64):
    logits, cache = model.prefill(
        params, {"tokens": jnp.asarray(prompt[None], jnp.int32)}, max_len
    )
    out = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(n - 1):
        lg, cache = model.decode_step(
            params, cache, jnp.asarray([[out[-1]]], jnp.int32)
        )
        out.append(int(jnp.argmax(lg[0, -1])))
    return out


@pytest.mark.parametrize(
    "layout", ["dense", "paged", "paged-telemetry", "paged-bounded"])
def test_engine_matches_isolated_decoding(layout):
    """Each request's tokens equal its isolated decode, in two passes over
    one engine; with telemetry on; and, behind a bounded queue that sheds
    the overflow, for every request it accepts."""
    model, params = build()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, size=L).astype(np.int32) for L in (5, 9, 7, 12, 6)]
    n_new = 6
    kw = {
        "paged-telemetry": dict(metrics=MetricsRegistry(),
                                trace=TraceRecorder()),
        "paged-bounded": dict(max_queue=3),
    }.get(layout, {})
    eng = Engine(model, params, slots=2, max_len=64,
                 cache_layout=layout.split("-")[0], page_size=8, **kw)
    want = {i: isolated_greedy(model, params, p, n_new)
            for i, p in enumerate(prompts)}
    for _ in range(2):
        accepted = set()
        for i, p in enumerate(prompts):
            try:
                eng.submit(Request(uid=i, prompt=p, max_new=n_new))
                accepted.add(i)
            except EngineOverloaded:
                assert layout == "paged-bounded"
        assert len(accepted) == (3 if layout == "paged-bounded" else 5)
        n_done = len(eng.done)
        done = eng.run()[n_done:]
        assert {r.uid for r in done} == accepted
        for req in done:
            assert req.output == want[req.uid], (req.uid, req.output)


def test_engine_ssm_family():
    model, params = build("ssm")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, size=L).astype(np.int32) for L in (4, 8, 6)]
    eng = Engine(model, params, slots=2, max_len=64)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new=4))
    done = eng.run()
    assert len(done) == 3
    for req in done:
        want = isolated_greedy(model, params, prompts[req.uid], 4)
        assert req.output == want, (req.uid, req.output, want)


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_engine_rejects_empty_prompt_and_zero_budget(layout):
    """Regression (_bucket edge cases): an empty prompt used to be padded
    to an 8-token bucket and the last-logits slice clamped to a wrong row
    (under-allocation of valid tokens); max_new=0 used to emit one token
    anyway.  Both are now rejected at submit."""
    model, params = build()
    eng = Engine(model, params, slots=1, max_len=64, cache_layout=layout,
                 page_size=8)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(uid=0, prompt=np.zeros((0,), np.int32), max_new=4))
    with pytest.raises(ValueError, match="max_new"):
        eng.submit(Request(uid=1, prompt=np.ones(4, np.int32), max_new=0))
    assert not eng.queue


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_engine_bucket_exact_max_len(layout):
    """Regression (_bucket edge cases): a prompt at the admission boundary
    (prompt + max_new == max_len, with max_len not a power of two) must
    bucket to a size that neither truncates the prompt nor overflows the
    cache, and produce the same tokens as unbucketed serving."""
    model, params = build()
    max_len = 48                                  # not a power of two
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 64, size=max_len - 4).astype(np.int32)
    outs = {}
    for bucket in (True, False):
        eng = Engine(model, params, slots=1, max_len=max_len,
                     cache_layout=layout, page_size=8, bucket_prompts=bucket)
        if bucket:
            # the pow-2 bucket (64) must clamp to max_len, never below
            # the prompt length
            assert eng._bucket(len(prompt)) == max_len
            assert eng._bucket(max_len) == max_len
            assert eng._bucket(3) == 8
        eng.submit(Request(uid=0, prompt=prompt, max_new=4))
        done = eng.run()
        assert len(done) == 1 and len(done[0].output) == 4
        outs[bucket] = done[0].output
    assert outs[True] == outs[False]
    want = isolated_greedy(model, params, prompt, 4, max_len=max_len)
    assert outs[True] == want


def test_engine_eos_early_stop():
    model, params = build()
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 64, size=6).astype(np.int32)
    # find the first greedy token, then use it as eos: request stops at len 1
    first = isolated_greedy(model, params, prompt, 1)[0]
    eng = Engine(model, params, slots=1, max_len=64)
    eng.submit(Request(uid=0, prompt=prompt, max_new=8, eos_id=first))
    done = eng.run()
    assert done[0].output == [first]
    assert done[0].finish_reason == "stop"


# ------------------------------------------------------- stop machinery
def test_stop_token_ids_via_params():
    """SamplingParams.stop_token_ids behaves like eos: the stop token is
    emitted, then the request finishes with reason "stop"."""
    from repro.serving.sampling import SamplingParams

    model, params = build()
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 64, size=7).astype(np.int32)
    ref = isolated_greedy(model, params, prompt, 6)
    stop_tok = ref[3]
    eng = Engine(model, params, slots=1, max_len=64)
    eng.submit(Request(uid=0, prompt=prompt,
                       params=SamplingParams(max_new=10,
                                             stop_token_ids=(stop_tok,))))
    done = eng.run()
    cut = ref.index(stop_tok) + 1
    assert done[0].output == ref[:cut]
    assert done[0].finish_reason == "stop"


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_stop_sequence_multi_token(layout):
    """A multi-token stop sequence fires only when the full suffix
    matches; matched tokens stay in the output."""
    from repro.serving.sampling import SamplingParams

    model, params = build()
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 64, size=9).astype(np.int32)
    ref = isolated_greedy(model, params, prompt, 8)
    seq = tuple(ref[2:4])
    # expected stop point: the FIRST prefix of ref whose suffix is the
    # full sequence (an untrained model may repeat tokens, so the pair
    # can complete earlier than index 3 — the stop rule, not a hardcoded
    # position, defines the truth)
    want = ref
    for n in range(len(seq), len(ref) + 1):
        if tuple(ref[n - len(seq):n]) == seq:
            want = ref[:n]
            break
    eng = Engine(model, params, slots=2, max_len=64, cache_layout=layout,
                 page_size=8)
    eng.submit(Request(uid=0, prompt=prompt,
                       params=SamplingParams(max_new=8,
                                             stop_sequences=(seq,))))
    # a second request must be unaffected by its neighbor stopping
    other = rng.integers(0, 64, size=5).astype(np.int32)
    eng.submit(Request(uid=1, prompt=other,
                       params=SamplingParams(max_new=6)))
    done = {r.uid: r for r in eng.run()}
    # stops exactly at the first FULL suffix match (a partial, single-
    # token overlap must not fire), matched tokens kept in the output
    assert done[0].output == want
    assert done[0].finish_reason == "stop"
    assert done[1].output == isolated_greedy(model, params, other, 6)


def test_stop_on_first_token_mid_chunked_prefill():
    """A request whose FIRST generated token (emitted as its chunked
    prefill completes, mid-stream between other requests' decode steps)
    is a stop token finishes without ever entering lockstep decode."""
    from repro.serving.sampling import SamplingParams

    model, params = build()
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 64, size=21).astype(np.int32)
    first = isolated_greedy(model, params, prompt, 1)[0]
    eng = Engine(model, params, slots=2, max_len=64, cache_layout="paged",
                 page_size=8, prefill_chunk=8)
    # keep a long-running decode in flight so the chunks interleave
    other = rng.integers(0, 64, size=4).astype(np.int32)
    eng.submit(Request(uid=1, prompt=other,
                       params=SamplingParams(max_new=12)))
    eng.submit(Request(uid=0, prompt=prompt,
                       params=SamplingParams(max_new=8,
                                             stop_token_ids=(first,))))
    done = {r.uid: r for r in eng.run()}
    assert done[0].output == [first]
    assert done[0].finish_reason == "stop"
    assert done[1].output == isolated_greedy(model, params, other, 12)


def test_params_without_max_new_inherits_request_budget():
    """Attaching sampling intent to a legacy request must not silently
    replace its explicit max_new (params.max_new=None inherits it)."""
    from repro.serving.sampling import SamplingParams

    model, params = build()
    rng = np.random.default_rng(10)
    prompt = rng.integers(0, 64, size=6).astype(np.int32)
    eng = Engine(model, params, slots=1, max_len=64)
    eng.submit(Request(uid=0, prompt=prompt, max_new=4,
                       params=SamplingParams(temperature=0.8, seed=1)))
    done = eng.run()
    assert len(done[0].output) == 4
    # an explicit params.max_new still wins over the legacy field
    eng.submit(Request(uid=1, prompt=prompt, max_new=4,
                       params=SamplingParams(max_new=7)))
    done = eng.run()
    assert len(done[-1].output) == 7


def test_eos_minus_one_never_stops():
    """eos_id=-1 (and no stop params) keeps the legacy never-stop
    semantics: the request always runs out its max_new budget."""
    model, params = build()
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 64, size=6).astype(np.int32)
    eng = Engine(model, params, slots=1, max_len=64)
    eng.submit(Request(uid=0, prompt=prompt, max_new=10, eos_id=-1))
    done = eng.run()
    assert len(done[0].output) == 10
    assert done[0].finish_reason == "length"


def test_cancel_queued_request_by_identity():
    """Engine.cancel must match by object identity: dataclass equality
    tuple-compares the numpy prompt field and raises on same-shape
    prompts (regression — uid reuse is common for raw-Engine callers)."""
    model, params = build()
    p = np.ones(6, np.int32)
    eng = Engine(model, params, slots=1, max_len=64)
    r1 = Request(uid=0, prompt=p.copy(), max_new=4)
    r2 = Request(uid=0, prompt=p.copy(), max_new=4)
    eng.submit(r1)
    eng.submit(r2)
    eng.cancel(r2)
    assert len(eng.queue) == 1 and eng.queue[0] is r1
    assert r2.finish_reason == "cancelled" and r2.output is None
    done = eng.run()
    assert any(r is r2 for r in done)
    r1_done = next(r for r in done if r is r1)
    assert len(r1_done.output) == 4 and r1_done.finish_reason == "length"


# ------------------------------------------- on-device selection regression
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_steady_state_step_single_bulk_transfer(layout, monkeypatch):
    """Acceptance: the jitted decode step selects tokens on device —
    a steady-state engine step performs exactly ONE bulk device->host
    transfer (the explicit device_get of the sampled tokens/logprobs)
    and NO implicit transfers (jax.transfer_guard("disallow") turns any
    stray int(jnp...)/np.asarray/jnp constant into an error)."""
    model, params = build()
    rng = np.random.default_rng(9)
    eng = Engine(model, params, slots=2, max_len=64, cache_layout=layout,
                 page_size=8)
    for i in range(2):   # fill every slot; queue empty => no admissions
        eng.submit(Request(uid=i, prompt=rng.integers(0, 64, size=6)
                           .astype(np.int32), max_new=40))
    eng.step()           # admissions + first decode (compiles)
    eng.step()           # warm steady state
    calls = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(1) or real_get(x))
    with jax.transfer_guard("disallow"):
        n = eng.step()
    assert n == 2
    assert len(calls) == 1, f"expected 1 bulk transfer, saw {len(calls)}"
