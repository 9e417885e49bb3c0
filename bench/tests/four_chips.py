"""A four-chip cell on four virtual CPU devices, as a sound run and with
the exchange between chips left out; run by test_bench_four_chips.py in
a process of its own:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python bench/tests/four_chips.py <root> <cell> <seed>

``root`` is a checkout holding the cell (the tests' ``tiny_root``). Prints
one JSON line: ``{"sound": <result>, "no_exchange": <result>}``.

The fault: each device takes the training step on its own rows with the
whole state, then keeps its own shard of the new state, so every shard
is updated from one device's gradient alone (no reduction over ``data``).
"""
from __future__ import annotations

import json
import os
import sys

import jax
from jax.sharding import PartitionSpec as P

ROOT, CELL, SEED = sys.argv[1], sys.argv[2], int(sys.argv[3])
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]

import run as R  # noqa: E402
from repro.core.config import ParallelConfig  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.training import train_step as TS  # noqa: E402


def own_shard(x, spec, axis="data"):
    """This device's block of the whole ``x`` under ``spec``."""
    n = jax.lax.axis_size(axis)
    k = jax.lax.axis_index(axis)
    for dim, name in enumerate(spec):
        names = name if isinstance(name, tuple) else (name,)
        if axis in names:
            size = x.shape[dim] // n
            x = jax.lax.dynamic_slice_in_dim(x, k * size, size, dim)
    return x


def no_exchange(model, tc):
    local = TS.make_train_step(build_model(model.cfg, ParallelConfig(), None), tc)
    specs = TS.train_state_specs(model)
    whole = jax.tree.map(lambda _: P(), specs)

    def per_device(state, batch):
        new, metrics = local(state, batch)
        return jax.tree.map(own_shard, new, specs), metrics

    f = jax.shard_map(per_device, mesh=model.ctx.mesh,
                      in_specs=(whole, P("data")), out_specs=(specs, P()),
                      check_vma=False)
    return jax.jit(f, donate_argnums=(0,))


def main():
    assert jax.device_count() == 4, jax.devices()
    args = R.parse(["--workload", CELL, "--seed", str(SEED), "--seconds", "2"])
    check = lambda jax_, chips: R.device_info(jax_)  # noqa: E731
    out = {"sound": R.run(args, root=ROOT, chips_check=check)}
    TS.make_sharded_train_step = no_exchange
    out["no_exchange"] = R.run(args, root=ROOT, chips_check=check)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
