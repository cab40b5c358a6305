"""SHA-256 of the StableHLO of every served configuration's step programs.

    python3 tools/program_hashes.py [--out FILE] [--only CONFIG,...]

A PR that adds a family or touches a shared op shows with it that the programs
the benchmark already had are the ones they were.  ``tools/program_hashes.txt``
is this file's ``--out`` on the tree as committed: a PR runs ``python3
tools/program_hashes.py --out FILE`` on its own tree, ``diff``s FILE against
the committed reading, says in its notes why each line that differs does (a
program it meant to change, and in what), and commits FILE as the new
``tools/program_hashes.txt``: one reading in the tree, not one a PR.  Where a
difference has to be read line by line, run the tool on the parent's archive
too (from any path) and compare the two programs' texts.  No chip: a CPU
process, about a minute.

What is hashed is the program and not where its source lies: ``as_text()``
leaves the StableHLO's locations out, but a Mosaic kernel rides in its custom
call as serialized MLIR that names the file and LINE of every Python frame
that traced it, so a docstring grown above a Pallas call site would "change"
every program that calls it.  ``_canonical`` puts in each kernel's place the
hash of its own text printed without locations.

Two readings a configuration of ``BENCHMARK.json`` that has an ``engine``:

* ``rehearsal``: ``LLMEngine`` itself at the configuration's tiny
  ``rehearsal`` sizes on the CPU, warmed up; every jitted step it called
  (decode, prefill, and where the engine has them verify and fork) lowered
  again at the operands of its first call (``StepRunner._first_operands``).
* ``v5e`` (families that bring their own layer programs: ``cache_kind``
  ``hybrid``, ``paged``, ``windowed`` or ``state``): the decode and the prefill chunk at the PUBLISHED
  sizes and the configuration's engine, lowered for a described v5e chip with
  the Pallas kernels on (the dispatch rules are told they are on a TPU), from
  shapes alone: nothing is compiled and nothing runs.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


#: a Mosaic kernel in a ``tpu_custom_call``'s ``backend_config``: base64 of MLIR bytecode
_KERNEL_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def _canonical(text: str) -> str:
    """``text`` with every Mosaic kernel replaced by the hash of its MLIR
    printed WITHOUT locations."""
    from jax._src.lib.mlir import ir

    def kernel(match) -> str:
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(match.group(2)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return match.group(1) + hashlib.sha256(asm.encode()).hexdigest() + match.group(3)

    return _KERNEL_BODY.sub(kernel, text)


def _sha(lowered) -> str:
    return hashlib.sha256(_canonical(lowered.as_text()).encode()).hexdigest()[:16]


def _rehearsal(config: dict, H) -> dict:
    from ray_tpu.llm import EngineConfig, LLMEngine
    from ray_tpu.serve.llm import _seeded_params

    sizes = H.sizes(config, True)
    cfg = H.family_piece(config, "model_config")(sizes)
    params = _seeded_params(H.family_piece(config, "program_init")(), cfg, 0,
                            sizes["engine"].get("tp", 1))
    engine = LLMEngine(cfg, params, EngineConfig(**sizes["engine"]))
    engine.warmup()
    return {site: _sha(fn.lower(*args, **static))
            for site, (fn, args, static) in sorted(engine.runner._first_operands.items())}


def _v5e(config: dict, H, one_chip) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.cache import KVBlockPool, LayerTypedConfig
    from ray_tpu.llm.model_runner import host_batch, pack_knobs
    from ray_tpu.llm.state_runner import HybridModelRunner, StateModelRunner

    sizes = H.sizes(config, False)
    cfg = H.family_piece(config, "model_config")(sizes)
    kind = getattr(cfg, "cache_kind", "kv")
    if kind not in ("hybrid", "paged", "windowed", "state"):
        return {}
    init, e = H.family_piece(config, "program_init")(), sizes["engine"]

    def sds(a):
        dtype = a.dtype if hasattr(a, "dtype") else np.asarray(a).dtype
        return jax.ShapeDtypeStruct(np.shape(a), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg)))
    slots = e["max_slots"]
    if kind == "state":  # ONE fixed-size state a layer and slot (cache.StatePool), no trash slot
        runner = StateModelRunner(cfg, params)
        body = runner.body
        pools = [jax.ShapeDtypeStruct((cfg.n_layers, slots) + tuple(body.state_shape),
                                      jnp.dtype(body.state_dtype), sharding=one_chip)]
        counts, width = [], 1
    else:
        runner = HybridModelRunner(cfg, params, e["block_size"])
        body, table = runner.body, e["max_blocks_per_seq"]
        lay = body.kv_layout()
        if kind == "windowed":  # K and V of each layer kind (cache.LayerTypedPool)
            geo = LayerTypedConfig(e["num_blocks"], e["block_size"], table, lay["window"],
                                   e["prefill_chunk"], slots)
            pool = [jax.ShapeDtypeStruct(
                (lay["kinds"][kind_], blocks, lay["n_heads"], e["block_size"], lay["head_dim"]),
                jnp.dtype(lay["dtype"]))
                for kind_, blocks in (("full", geo.num_blocks), ("window", geo.window_num_blocks))
                for _ in range(2)]
            table *= 2  # a row is both kinds' tables
        else:
            shape = (lay["n_layers"], e["num_blocks"], lay["n_heads"], e["block_size"],
                     lay["head_dim"])
            pool = [jax.ShapeDtypeStruct(shape, jnp.dtype(lay["dtype"]))] * KVBlockPool.n_arrays(
                **lay)
        hybrid = kind == "hybrid"
        leaves = body.state_leaves(e["block_size"]) if hybrid else {}
        pools = [sds(p) for p in pool] + [
            jax.ShapeDtypeStruct((n, slots + 1, *shape), jnp.dtype(dt), sharding=one_chip)
            for n, shape, dt in leaves.values()]
        counts = [sds(c) for c in getattr(body, "counters", tuple)()]
        width = table + (1 if hybrid else 0)
    z, i32 = np.zeros(slots), np.int32
    decode = [sds(o) for o in host_batch(
        z.astype(i32), z.astype(i32), np.zeros((slots, width), i32), z, z, np.ones(slots), z, z)]
    chunk = e["prefill_chunk"]
    prefill = [sds(o) for o in (np.zeros(chunk, i32), i32(0), i32(chunk), np.zeros(width, i32),
                                pack_knobs(0, 0.0, 0, 1.0, 0))]
    return {
        "decode": _sha(runner._decode.lower(params, *pools, *counts, *decode)),
        "prefill": _sha(runner._prefill.lower(params, *pools, *counts, *prefill, chunk=chunk)),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness as H
    from ray_tpu.ops import latent_attention, moe, paged_attention, power_retention, ssd

    man = H.manifest()
    names = [c["name"] for c in man["configs"]]
    if args.only:
        names = [n for n in names if n in args.only.split(",")]
    report = {}
    for name in names:
        config = H.load_config(man, name)
        if "engine" not in config:
            continue
        try:
            report[name] = {"rehearsal": _rehearsal(config, H)}
        except Exception as e:  # say so and go on: the other programs still count
            report[name] = {"rehearsal": f"not lowered: {type(e).__name__}: {e}"[:200]}
        print(json.dumps({name: report[name]}), flush=True)
    # the kernels' side: every dispatch rule believes it is on a TPU from here
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for module in (paged_attention, moe, latent_attention, ssd, power_retention):
        module._on_tpu = lambda: True
    for name in names:
        config = H.load_config(man, name)
        if "engine" not in config or config["engine"].get("tp", 1) > 1:
            continue
        try:
            got = _v5e(config, H, one_chip)
        except Exception as e:
            got = f"not lowered: {type(e).__name__}: {e}"[:200]
        if got:
            report[name]["v5e"] = got
            print(json.dumps({name: {"v5e": got}}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
