"""Sharded serving: tensor-parallel inference on the mesh, proven correct
by cross-mesh parity.

Every test runs in a subprocess with 8 virtual CPU devices (the XLA
device-count flag must be set before jax initializes; the main pytest
process stays at 1 device per the project rules).  Inside the subprocess a
single-device reference engine (mesh=None) and mesh engines on (1,8) and
(2,4) serve the same mixed greedy + seeded-sampled workload across all
three cache layouts (dense / paged / prefix+chunk); outputs must be
token-identical — the replicated logits row makes per-request sampling
seeds mesh-shape-independent.

The transfer-guard test re-pins the serving one-bulk-transfer-per-step
contract on the mesh: a steady-state decode step under
``jax.transfer_guard("disallow")`` performs exactly one ``jax.device_get``.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# 8 heads / 8 kv heads so every tested mesh's model axis divides the head
# dim — placement shardings require exact divisibility (sharding.fit_spec
# degrades uneven dims to replication, but the point here is to exercise
# the *sharded* pool).
_COMMON = textwrap.dedent("""
    import jax, numpy as np
    from repro.core.config import ModelConfig, ParallelConfig
    from repro.models.model import build_model
    from repro.serving.engine import Engine, Request
    from repro.serving.sampling import SamplingParams
    from repro.launch.mesh import make_mesh

    CFG = ModelConfig(name="smoke", family="dense", num_layers=2,
                      d_model=64, num_heads=8, num_kv_heads=8, d_ff=128,
                      vocab_size=64, dtype="float32")
    PARAMS = build_model(CFG).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    PROMPTS = [rng.integers(1, CFG.vocab_size, size=n).astype(np.int32)
               for n in (5, 11, 17, 9)]

    def make_engine(mesh, **kw):
        model = build_model(CFG, ParallelConfig(), mesh)
        return Engine(model, PARAMS, slots=3, max_len=64, **kw)

    def serve(mesh, **kw):
        eng = make_engine(mesh, **kw)
        for i, p in enumerate(PROMPTS):
            sp = (None if i % 2 == 0 else
                  SamplingParams(temperature=0.8, top_k=12, seed=40 + i))
            eng.submit(Request(uid=i, prompt=p, max_new=8, params=sp))
        eng.run()
        assert len(eng.done) == len(PROMPTS)
        return {r.uid: tuple(r.output) for r in eng.done}
""")

_PARITY = _COMMON + textwrap.dedent("""
    LAYOUTS = {
        "dense": dict(cache_layout="dense"),
        "paged": dict(cache_layout="paged", page_size=8),
        "prefix+chunk": dict(cache_layout="paged", page_size=8,
                             prefix_cache=True, prefill_chunk=8),
    }
    mesh = make_mesh(__MESH__, ("data", "model"))
    for name, kw in LAYOUTS.items():
        ref = serve(None, **kw)
        got = serve(mesh, **kw)
        assert got == ref, (name, ref, got)
        print("OK", name)
""")


@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4)])
def test_mesh_parity_all_layouts(mesh_shape):
    out = run_py(_PARITY.replace("__MESH__", repr(mesh_shape)))
    assert out.count("OK") == 3, out


def test_mesh_decode_single_bulk_transfer():
    """Steady-state sharded decode keeps the one-device_get-per-step
    contract: no host->device uploads, exactly one bulk download."""
    code = _COMMON + textwrap.dedent("""
        mesh = make_mesh((2, 4), ("data", "model"))
        eng = make_engine(mesh, cache_layout="paged", page_size=8)
        for i, p in enumerate(PROMPTS[:3]):
            eng.submit(Request(uid=i, prompt=p, max_new=16))
        for _ in range(4):        # admit + settle into steady-state decode
            eng.step()
        real_get = jax.device_get
        calls = []
        jax.device_get = lambda x: (calls.append(1), real_get(x))[1]
        try:
            with jax.transfer_guard("disallow"):
                n = eng.step()
        finally:
            jax.device_get = real_get
        assert n > 0, "decode step emitted no tokens"
        assert len(calls) == 1, f"expected 1 bulk device_get, saw {len(calls)}"
        print("OK transfer", n, len(calls))
    """)
    assert "OK transfer" in run_py(code)


_CHURN = textwrap.dedent("""
    import sys
    sys.path.insert(0, __TESTS__)
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    from test_prefix_cache import Churn, PAGE
    from repro.launch.mesh import make_mesh

    MESH = make_mesh((1, 8), ("data", "model"))
    HKV, D = 8, 4

    class ShardedChurn(Churn):
        '''Churn's shadow content model, backed by a real device pool
        sharded over the KV-head (model) axis.  Every shadow write —
        prefill block, COW page copy — is mirrored into the sharded
        pool through the same ref==1 discipline, so any disagreement
        between the global host allocator and the per-shard device
        pools (a write into a shared page, a lost COW copy, a stale
        hash hit) shows up as a content mismatch.'''

        def __init__(self):
            super().__init__()
            self.sh = NamedSharding(MESH,
                                    PartitionSpec(None, None, "model", None))
            self.pool = jax.device_put(
                jnp.zeros((self.al.num_pages, PAGE, HKV, D), jnp.float32),
                self.sh)
            churn = self

            class Mirror(dict):
                def __setitem__(self, page, blk):
                    dict.__setitem__(self, page, blk)
                    churn._dev_write(page, blk)

            self.content = Mirror()

        def _dev_write(self, page, blk):
            if blk is None:
                return
            tok = jnp.asarray(np.asarray(blk, np.float32))
            tile = jnp.broadcast_to(tok[:, None, None], (PAGE, HKV, D))
            self.pool = jax.device_put(self.pool.at[page].set(tile), self.sh)

        def live_pages(self):
            pages = set()
            for slot in self.active:
                pages.update(int(p) for p in self.al.owned(slot))
            pages.update(int(p) for p in self.al._evictable)  # parked cached
            return pages

        def verify(self):
            assert len(self.pool.sharding.device_set) == 8, "pool unsharded"
            host = np.asarray(jax.device_get(self.pool))
            live = self.live_pages()
            for page in live:
                blk = self.content.get(page)
                if blk is None:
                    continue
                want = np.broadcast_to(
                    np.asarray(blk, np.float32)[:, None, None],
                    (PAGE, HKV, D))
                np.testing.assert_array_equal(
                    host[page], want,
                    err_msg=f"device pool disagrees on page {page}")
            # per-shard consistency: each device's head-slice of a live
            # page holds the same broadcast tokens — shards never drift
            for shard in self.pool.addressable_shards:
                data = np.asarray(shard.data)
                for page in sorted(live)[:2]:
                    blk = self.content.get(page)
                    if blk is None:
                        continue
                    want = np.broadcast_to(
                        np.asarray(blk, np.float32)[:, None, None],
                        data[page].shape)
                    np.testing.assert_array_equal(data[page], want)

        def apply(self, op):
            super().apply(op)
            self.verify()

    rng = np.random.default_rng(0)
    OPS = ((0, 8), (0, 64), (0, 12), (0, 64), (0, 64))
    for ex in range(40):
        churn = ShardedChurn()
        for _ in range(int(rng.integers(1, 31))):
            churn.apply(tuple(int(rng.integers(lo, hi + 1))
                              for lo, hi in OPS))
        churn.finish()
    print("OK churn")
""")


def test_sharded_kv_pool_churn_property():
    """Allocate/free/evict/COW churn on an 8-device mesh: the global host
    allocator and the per-shard device pools must never disagree (hash
    hits return matching pages; COW writes touch only exclusive pages)."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    out = run_py(_CHURN.replace("__TESTS__", repr(tests_dir)))
    assert "OK churn" in out


def test_cache_shardings_shard_kv_over_model_axis():
    """The paged K/V pools actually shard over the head axis (the point of
    tensor-parallel serving): each device holds 1/model-axis of the pool,
    while block tables / pos stay replicated for host-side paging."""
    code = _COMMON + textwrap.dedent("""
        mesh = make_mesh((2, 4), ("data", "model"))
        eng = make_engine(mesh, cache_layout="paged", page_size=8)
        k_pool = eng.cache["layers"]["sub0"]["attn"]["k_pool"]
        shard_shape = k_pool.sharding.shard_shape(k_pool.shape)
        assert shard_shape[3] == k_pool.shape[3] // 4, (
            k_pool.shape, shard_shape)
        bt = eng.cache["block_table"]
        assert bt.sharding.is_fully_replicated
        assert eng.cache["pos"].sharding.is_fully_replicated
        print("OK shards", k_pool.shape, shard_shape)
    """)
    assert "OK shards" in run_py(code)
