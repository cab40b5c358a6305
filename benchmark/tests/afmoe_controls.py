"""Controls for ``trinity-large-ep8-l5-1chip``'s ``logit_tolerance`` and
``routing_margin``: what the reference comparison reads on the configured
programs over four probe seeds, on programs held one precision lower in ONE
place each, and on programs with ONE of Trinity's own mechanisms broken.

    python3 benchmark/tests/afmoe_controls.py [--rehearsal] [--out FILE] [--only a,b]

One process, on the chip at the published widths (``--rehearsal``: the
configuration's tiny sizes on a CPU, to try the script).  The served programs
(``HybridModelRunner``'s prefill chunk and decode at the engine's
``prefill_chunk``, block size and table, over a ``LayerTypedPool`` that is
slid before every step as the engine slides it, the decode donating the pools)
answer the configuration's probe prompts greedily, one probe at a time in row
1 of a full decode batch; the statistic is ``reference_check``'s: at each
output position the reference's logit of the program's token against the
reference's largest, through the family's ``reference_logits`` (the rows
within ``routing_margin`` of a flip say nothing).  A departure is planted
HERE, by overriding one method of the body or of the pool, a field of the
configuration the PROGRAM is given, or rounding the weights it is given: the
served programs hold no such switch, and the reference is always given the
configuration as it is.

* ``configured``: what the cell serves, on the configuration's probe seed;
  with it ``rows_by_margin``: the largest deficit among the rows whose routing
  margin (the reference's own) lies under each of a few sizes, and among the
  rest, with no margin applied: what ``routing_margin`` is chosen from.
  ``configured_seed<n>``: the same programs on three more probe seeds.
* ``mantissa3_kv``: every key and value rounded to 3 bits of mantissa
  (float8_e4m3's) on its way into both K/V pools.  Must fail.
* ``mantissa3_weights``: every weight matrix rounded to 3 bits of mantissa
  (the reference keeps the weights as they are).  Must fail.
* ``window_plus_a_block``: the program's window a block longer (4,096 + 128
  keys; its pool and its kernels follow, the reference's stays).  Must fail.
* ``rotary_in_full_layer``: q and k turn in the full layer too.  Must fail.
* ``gate_dropped``: the attention's output without its sigmoid gate.  Must fail.
* ``released_block_read``: the pool hands a window block back one block EARLY,
  so the walk of every row past the window reads, unmasked, an entry its pool
  has taken back (the trash block there).  Must fail.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness as H  # noqa: E402
from benchmark import serving  # noqa: E402
from benchmark.tests.falcon_h1_controls import _sequences  # noqa: E402
from benchmark.tests.kimi_controls import _to_mantissa3  # noqa: E402
from benchmark.tests.phi4flash_controls import _deficits  # noqa: E402

CONFIG = "trinity-large-ep8-l5-1chip"
#: probe seeds beside the configuration's own
MORE_SEEDS = (11, 20260517, 3000000019)
#: ``rows_by_margin``: the sizes of the routing margin the rows are split at
MARGINS = (0.0005, 0.001, 0.002, 0.004, 0.008, 0.016)


def _controls(cfg, block: int) -> dict:
    """name -> (the configuration the PROGRAM is given, the pool's class)."""
    import jax.numpy as jnp

    from ray_tpu.llm.cache import LayerTypedPool
    from ray_tpu.models.afmoe import AfmoeBody

    class Mantissa3KV(AfmoeBody):
        def _qkv(self, h, layer, positions, turns):
            q, k, v, g = super()._qkv(h, layer, positions, turns)
            return q, _to_mantissa3(k), _to_mantissa3(v), g

    class RotaryInFull(AfmoeBody):
        def _qkv(self, h, layer, positions, turns):
            return super()._qkv(h, layer, positions, True)

    class GateDropped(AfmoeBody):
        def _gate(self, att, g):
            return att.astype(jnp.float32).reshape(g.shape)

    class EarlyRelease(LayerTypedPool):
        """Hands back as if the window were one block shorter."""

        def slide(self, seq_id, start, n):
            real = self.cfg
            self.cfg = dataclasses.replace(real, window=real.window - real.block_size)
            try:
                super().slide(seq_id, start, n)
            finally:
                self.cfg = real

    def with_body(body):
        class Config(type(cfg)):
            def serving_body(self):
                return body(self)

        return Config(**{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)})

    return {
        "configured": (cfg, LayerTypedPool),
        "mantissa3_kv": (with_body(Mantissa3KV), LayerTypedPool),
        "window_plus_a_block": (dataclasses.replace(cfg, window=cfg.window + block),
                                LayerTypedPool),
        "rotary_in_full_layer": (with_body(RotaryInFull), LayerTypedPool),
        "gate_dropped": (with_body(GateDropped), LayerTypedPool),
        "released_block_read": (cfg, EarlyRelease),
    }


class _Served:
    """The served programs of one model configuration over one pool, compiled
    once, for several sets of probes.  The decode DONATES the pools and the
    ledger, as the engine's own step does."""

    def __init__(self, cfg, params, engine: dict, pool_cls):
        import jax

        from ray_tpu.llm.cache import LayerTypedConfig
        from ray_tpu.llm.model_runner import pack_knobs
        from ray_tpu.llm.state_runner import HybridModelRunner

        self.engine = engine
        self.runner = HybridModelRunner(cfg, params, engine["block_size"])
        layout = self.runner.body.kv_layout()
        self.pool = pool_cls(LayerTypedConfig(
            engine["num_blocks"], engine["block_size"], engine["max_blocks_per_seq"],
            window=layout["window"], chunk=engine["prefill_chunk"], slots=engine["max_slots"]),
            layout)
        self.greedy = pack_knobs(0, 0.0, 0, 1.0, 0)
        self.decode = jax.jit(self.runner._decode_logits, donate_argnums=(1,))

    def probes(self, probes: list, keep_logits: bool):
        """Each probe greedily through the served programs.  Returns (outs,
        logit rows a probe or None)."""
        import numpy as np

        runner, pool = self.runner, self.pool
        chunk, slots = self.engine["prefill_chunk"], self.engine["max_slots"]
        outs, rows = [], []
        for n, probe in enumerate(probes):
            prompt, want = probe["prompt"], probe["max_tokens"]
            name = f"probe{n}"
            pool.allocate(name, len(prompt) + want)
            for pos in range(0, len(prompt), chunk):
                piece = prompt[pos:pos + chunk]
                buf = np.zeros(chunk, np.int32)
                buf[:len(piece)] = piece
                pool.slide(name, pos, len(piece))
                *arrays, logits, _, _ = runner.prefill_chunk(
                    *pool.arrays, buf, pos, len(piece), pool.table_row(name), self.greedy)
                pool.arrays = arrays
            counts = runner._counts
            out, got = [], []
            for i in range(want):
                row = np.asarray(logits, np.float32)
                got.append(row)
                out.append(int(row.argmax()))
                if i + 1 == want:
                    break
                tokens, positions = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
                tokens[1], positions[1] = out[-1], len(prompt) + i
                pool.slide(name, len(prompt) + i, 1)
                tables = np.stack([pool.table_row(None)] * slots)
                tables[1] = pool.table_row(name)
                arrays, batch = self.decode(
                    runner.params, (*pool.arrays, *counts), tokens, positions, tables)
                pool.arrays, counts = arrays[:4], arrays[4:]
                logits = batch[1]
            runner._counts = tuple(counts)
            pool.free(name)
            outs.append(out)
            rows.append(np.stack(got) if keep_logits else None)
        return outs, rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--only", default=None, help="comma-separated controls")
    args = ap.parse_args()
    H.prepare_environment(args.rehearsal)
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import numpy as np

    from benchmark.reference import afmoe as reference
    from ray_tpu.serve.llm import _seeded_params

    config = H.load_config(H.manifest(), CONFIG)
    sizes = H.sizes(config, args.rehearsal)
    cfg = H.family_piece(config, "model_config")(sizes)
    consts = H.family_piece(config, "reference_sizes")(cfg)
    margin = H.family_piece(config, "routing_margin")(cfg)
    init = H.family_piece(config, "program_init")()
    seed = config["deployment"]["weights_seed"]
    params = _seeded_params(init, cfg, seed, 1)
    probes = serving.probe_prompts(config, cfg.vocab_size, args.rehearsal)
    tol = sizes["correctness"]["logit_tolerance"]
    report = {"platform": jax.devices()[0].platform, "tolerance": tol, "routing_margin": margin,
              "prompt_lens": [len(p["prompt"]) for p in probes], "controls": {}}

    def save():
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)

    def probes_of(probe_seed: int) -> list:
        over = dict(sizes["correctness"], probe_seed=probe_seed)
        seeded = dict(config, correctness=over)
        if args.rehearsal:
            seeded["rehearsal"] = dict(config["rehearsal"], correctness=over)
        return serving.probe_prompts(seeded, cfg.vocab_size, args.rehearsal)

    def judge(name, these, outs, rows):
        """``reference_check``'s statistic with the family's margin, and
        beside it every row's deficit with NO margin against its margin."""
        per_probe, distance, raw, near = [], [], [], []
        for (seq, at), out, got in zip(_sequences(these, outs), outs, rows):
            want, margins = (np.asarray(x) for x in reference.logits_and_margins(
                params, seq, at, consts))
            deficit = _deficits(want, out)
            raw.append(deficit)
            near.append(margins)
            per_probe.append(float(np.where(margins >= margin, deficit, 0.0).max()))
            if got is not None:
                distance.append((got - want)[margins >= margin])
        raw, near = np.concatenate(raw), np.concatenate(near)
        worst = max(per_probe)
        biggest = lambda d: float(d.max()) if d.size else None  # noqa: E731
        line = {"max_deficit": worst, "per_probe_max": per_probe, "ok": bool(worst <= tol),
                "rows": int(raw.size), "rows_compared": int((near >= margin).sum()),
                "max_deficit_no_margin": float(raw.max()),
                "rows_by_margin": {str(m): {"rows_under": int((near < m).sum()),
                                            "max_deficit_under": biggest(raw[near < m]),
                                            "max_deficit_over": biggest(raw[near >= m])}
                                   for m in MARGINS}}
        if distance:
            err = np.concatenate(distance)
            line["logit_rows"] = {"rms": float(np.sqrt((err**2).mean())),
                                  "max_abs": float(np.abs(err).max())}
        report["controls"][name] = line
        H.emit("control", name=name, **line)
        save()

    controls = _controls(cfg, sizes["engine"]["block_size"])
    names = args.only.split(",") if args.only else [*controls, "mantissa3_weights"]
    for name in names:
        if name not in controls:
            continue
        program_cfg, pool_cls = controls[name]
        programs = _Served(program_cfg, params, sizes["engine"], pool_cls)
        served = {name: (probes, *programs.probes(probes, keep_logits=True))}
        if name == "configured":
            for s in MORE_SEEDS:
                more = probes_of(s)
                served[f"configured_seed{s}"] = (more, *programs.probes(more, False))
        del programs
        gc.collect()
        H.note(f"{name}: served")
        for key, run in served.items():
            judge(key, *run)

    if "mantissa3_weights" in names:
        # last: the matrices are rounded where they lie (two trees do not fit
        # the chip), served, and made anew from the seed for the reference
        rounded = jax.jit(_to_mantissa3, donate_argnums=0)(params)
        del params
        programs = _Served(cfg, rounded, sizes["engine"], controls["configured"][1])
        outs, rows = programs.probes(probes, keep_logits=True)
        del rounded, programs
        gc.collect()
        params = _seeded_params(init, cfg, seed, 1)
        judge("mantissa3_weights", probes, outs, rows)


if __name__ == "__main__":
    main()
