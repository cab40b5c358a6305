"""Controls for ``falcon-h1-34b-l8-1chip``'s ``logit_tolerance``: what the
reference comparison reads on the configured programs over four probe seeds,
and on programs held one precision lower in ONE place each.

    python3 benchmark/tests/falcon_h1_controls.py [--rehearsal] [--out FILE] [--only a,b]

One process, on the chip at the published widths (``--rehearsal``: the
configuration's tiny sizes on a CPU, to try the script).  The served programs
(``HybridModelRunner``'s prefill chunk and decode at the engine's
``prefill_chunk``, block size and table) answer the configuration's probe
prompts greedily, one probe at a time in row 1 of a full decode batch; the
statistic is ``reference_check``'s: at each output position the reference's
logit of the program's token against the reference's largest.  A departure is
planted HERE, by overriding one method of the body or one field of the
model's configuration: the served programs hold no such switch.

* ``configured``: what the cell serves, on the configuration's probe seed.
  Every control on that seed also gives the program's whole logit rows
  against the reference's (rms, largest).
  ``configured_seed<n>``: the same programs on three more probe seeds.
* ``bf16_ssd_state``: the SSD state held in bfloat16 in the pool.  Reads
  what ``configured`` reads on the chip and PASSES (a finding, not a fault:
  one more rounding of the activations' own size, beside some hundred
  bfloat16 products a token; ``A`` in [1, 16] and ``dt`` in [1e-3, 0.1]
  leave a state nothing to stagnate on).
* ``mantissa3_ssd_state``: the pool of SSD states rounded to 3 bits of
  mantissa (float8_e4m3's) after every step that wrote it.  Must fail.
* ``mantissa3_kv``: every key and value rounded to 3 bits of mantissa
  (float8_e4m3's) on its way into the K/V pool.  Must fail.
* ``mantissa3_weights``: every weight matrix rounded to 3 bits of mantissa
  (the reference keeps the weights as they are).  Must fail.
* ``witness`` (says something on a chip only): the plain reference ITSELF
  with its matrix products at the chip's default precision (operands rounded
  to bfloat16, float32 sums) against itself at ``highest``, on the configured
  run's sequences: if bf16 products alone, in code with no cache, no chunk and
  no kernel, read what the program reads, the program's distance is rounding.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness as H  # noqa: E402
from benchmark import serving  # noqa: E402
from benchmark.tests.phi4flash_controls import _deficits, _to_mantissa3  # noqa: E402

CONFIG = "falcon-h1-34b-l8-1chip"
MANTISSA3 = "mantissa3_weights"
#: probe seeds beside the configuration's own
MORE_SEEDS = (11, 20260517, 3000000019)


def _controls(cfg):
    from ray_tpu.models.falcon_h1 import FalconH1Body

    class Mantissa3KV(FalconH1Body):
        def _qkv(self, u, layer, positions):
            q, k, v = super()._qkv(u, layer, positions)
            return q, _to_mantissa3(k), _to_mantissa3(v)

    class Mantissa3State(FalconH1Body):
        """The pool of SSD states rounded after every step that wrote it."""

        def decode(self, *args):
            hidden, (k, v, conv, ssd) = super().decode(*args)
            return hidden, (k, v, conv, _to_mantissa3(ssd))

        def chunk(self, *args):
            hidden, (k, v, conv, ssd) = super().chunk(*args)
            return hidden, (k, v, conv, _to_mantissa3(ssd))

    def with_body(body):
        class Config(type(cfg)):
            def serving_body(self):
                return body(self)

        return Config(**dataclasses.asdict(cfg))

    return {
        "configured": cfg,
        "bf16_ssd_state": dataclasses.replace(cfg, state_dtype="bfloat16"),
        "mantissa3_ssd_state": with_body(Mantissa3State),
        "mantissa3_kv": with_body(Mantissa3KV),
    }


class _Served:
    """The served programs of one model configuration, compiled once, for
    several sets of probes."""

    def __init__(self, cfg, params, engine: dict):
        import jax

        from ray_tpu.llm.cache import HybridConfig, HybridPool
        from ray_tpu.llm.model_runner import pack_knobs
        from ray_tpu.llm.state_runner import HybridModelRunner

        self.engine = engine
        bs, slots = engine["block_size"], engine["max_slots"]
        self.runner = HybridModelRunner(cfg, params, bs)
        self.pool = HybridPool(
            HybridConfig(engine["num_blocks"], bs, engine["max_blocks_per_seq"], slots),
            self.runner.body.kv_layout(), self.runner.body.state_leaves(bs))
        self.greedy = pack_knobs(0, 0.0, 0, 1.0, 0)
        # the pools donated, as the served decode has them: a second copy of
        # 3.9 GB does not fit beside the weights
        self.step = jax.jit(self.runner._decode_logits, donate_argnums=(1,))

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
            table = pool.table_row(name)
            for pos in range(0, len(prompt), chunk):
                piece = prompt[pos:pos + chunk]
                buf = np.zeros(chunk, np.int32)
                buf[:len(piece)] = piece
                *arrays, logits, _, _ = runner.prefill_chunk(
                    *pool.arrays, buf, pos, len(piece), table, self.greedy)
                pool.arrays = arrays
            tables = np.stack([pool.table_row(None)] * slots)
            tables[1] = table
            out, got = [], []
            for i in range(want):
                row = np.asarray(logits, np.float32)
                got.append(row)
                out.append(int(row.argmax()))
                if i + 1 == want:
                    break
                tokens, positions = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
                tokens[1], positions[1] = out[-1], len(prompt) + i
                pool.arrays, batch = self.step(
                    runner.params, pool.arrays, tokens, positions, tables)
                logits = batch[1]
            pool.free(name)
            outs.append(out)
            rows.append(np.stack(got) if keep_logits else None)
        return outs, rows


def _sequences(probes, outs):
    """As ``reference_check``: prompt and chosen tokens padded to one width
    (causal: padding is inert), and the output rows."""
    width = max(len(p["prompt"]) + len(o) for p, o in zip(probes, outs))
    for probe, out in zip(probes, outs):
        prompt = probe["prompt"]
        yield ((prompt + out[:-1] + [0] * width)[:width],
               list(range(len(prompt) - 1, len(prompt) - 1 + len(out))))


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

    from ray_tpu.serve.llm import _seeded_params

    config = H.load_config(H.manifest(), CONFIG)
    sizes = H.sizes(config, args.rehearsal)
    cfg = H.family_piece(config, "model_config")(sizes)
    reference_logits = H.family_piece(config, "reference_logits")
    init = H.family_piece(config, "program_init")()
    seed = config["deployment"]["weights_seed"]
    params = _seeded_params(init, cfg, seed, 1)
    probes = serving.probe_prompts(config, cfg.vocab_size, args.rehearsal)
    tol = sizes["correctness"]["logit_tolerance"]
    report = {"platform": jax.devices()[0].platform, "tolerance": tol,
              "prompt_lens": [len(p["prompt"]) for p in probes], "controls": {}}

    def probes_of(probe_seed: int) -> list:
        over = dict(sizes["correctness"], probe_seed=probe_seed)
        key = "rehearsal" if args.rehearsal else None
        seeded = dict(config, correctness=over)
        if key:
            seeded[key] = dict(config[key], correctness=over)
        return serving.probe_prompts(seeded, cfg.vocab_size, args.rehearsal)

    controls = _controls(cfg)
    names = args.only.split(",") if args.only else list(controls) + [MANTISSA3]

    def judge(name, these, outs, rows):
        per_probe, distance = [], []
        for (seq, at), out, got in zip(_sequences(these, outs), outs, rows):
            want = np.asarray(reference_logits(params, seq, at, cfg))
            per_probe.append(float(_deficits(want, out).max()))
            if got is not None:
                distance.append(got - want)
        worst = max(per_probe)
        line = {"max_deficit": worst, "per_probe_max": per_probe, "ok": bool(worst <= tol)}
        if distance:
            err = np.concatenate(distance)
            line["logit_rows"] = {"rms": float(np.sqrt((err**2).mean())),
                                  "max_abs": float(np.abs(err).max())}
        report["controls"][name] = line
        H.emit("control", name=name, **line)

    served = {}
    for name in names:
        if name == MANTISSA3:
            continue
        programs = _Served(controls[name], params, sizes["engine"])
        served[name] = (probes, *programs.probes(probes, keep_logits=True))
        if name == "configured":
            for s in MORE_SEEDS:
                more = probes_of(s)
                served[f"configured_seed{s}"] = (more, *programs.probes(more, False))
        del programs
        gc.collect()
        H.note(f"{name}: served")
    for name, (these, outs, rows) in served.items():
        judge(name, these, outs, rows)

    if "configured" in served:  # tells something on a chip only
        per_probe, distance = [], []
        for seq, at in _sequences(probes, served["configured"][1]):
            want = np.asarray(reference_logits(params, seq, at, cfg))
            with mock.patch.object(jax, "default_matmul_precision",
                                   lambda _, real=jax.default_matmul_precision: real("bfloat16")):
                got = np.asarray(reference_logits(params, seq, at, cfg))
            per_probe.append(float(_deficits(want, got.argmax(axis=-1)).max()))
            distance.append(got - want)
        err = np.concatenate(distance)
        line = {"max_deficit": max(per_probe), "per_probe_max": per_probe,
                "logit_rows": {"rms": float(np.sqrt((err**2).mean())),
                               "max_abs": float(np.abs(err).max())}}
        report["witness"] = line
        H.emit("witness", **line)

    if MANTISSA3 in names:
        # last: the weights are rounded where they lie (two trees do not fit
        # the chip), served, and made anew from the seed for the reference
        rounded = jax.jit(_to_mantissa3, donate_argnums=0)(params)
        del params
        programs = _Served(cfg, rounded, sizes["engine"])
        outs, rows = programs.probes(probes, keep_logits=True)
        del rounded, programs
        gc.collect()
        params = _seeded_params(init, cfg, seed, 1)
        judge(MANTISSA3, probes, outs, rows)

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
