"""Controls for ``granite-4.0-h-small-ep2-l10-1chip``'s ``logit_tolerance``
and for what a flipped routing choice costs: what the reference comparison
reads on the configured programs over four probe seeds, on programs held one
precision lower in ONE place each, and on the plain reference itself with its
products' inputs rounded to bfloat16.

    python3 benchmark/tests/granite_h_controls.py [--rehearsal] [--out FILE] [--only a,b]

One process, on the chip at the published widths (``--rehearsal``: the
configuration's tiny sizes on a CPU, to try the script).  The served programs
(``HybridModelRunner``'s prefill chunk and decode at the engine's
``prefill_chunk``, block size and table) answer the configuration's probe
prompts greedily, one probe at a time in row 1 of a full decode batch; the
statistic is ``reference_check``'s: at each output position the reference's
logit of the program's token against the reference's largest, through the
family's ``reference_logits`` (EVERY row: the configuration's routing margin
is 0).  A departure is planted HERE, by overriding one method of the body:
the served programs hold no such switch.

* ``configured``: what the cell serves, on the configuration's probe seed.
  Every control on that seed also gives the program's whole logit rows
  against the reference's (rms, largest).  ``configured_seed<n>``: the same
  programs on three more probe seeds.
* ``mantissa3_ssd_state``: the pool of SSD states rounded to 3 bits of
  mantissa (float8_e4m3's) after every step that wrote it.  Must fail.
* ``mantissa3_kv``: every key and value rounded to 3 bits of mantissa on its
  way into the K/V pool.  Must fail.
* ``mantissa3_weights``: every weight matrix rounded to 3 bits of mantissa
  (the reference keeps the weights as they are).  Must fail.
* ``bf16_router``: the router's input and product in bfloat16 (one pass).
  Not a precision below the configured path, whose every other product
  already rounds its inputs to bfloat16: reported as found.
* ``witness`` (says something on a chip only): the plain reference ITSELF
  with its matrix products at the chip's default precision (operands rounded
  to bfloat16, float32 sums; no cache, no chunk, no kernel, no tiles) against
  itself at ``highest``, on the configured run's sequences: its deficits, and
  the ROUTING FLIPS between the two (positions where some layer's set of
  chosen held experts differs), with the deficits of the rows that flipped
  beside those of the rows that did not, and the reference's ``gap`` and
  ``weight`` at each flip.  ``rows_by_gap`` on the configured run: the
  largest deficit among the rows whose least ``gap`` over the layers lies
  under each of a few sizes, and among the rest.
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
from benchmark.tests.falcon_h1_controls import _sequences  # noqa: E402
from benchmark.tests.kimi_controls import _to_mantissa3  # noqa: E402
from benchmark.tests.phi4flash_controls import _deficits  # noqa: E402

CONFIG = "granite-4.0-h-small-ep2-l10-1chip"
MANTISSA3 = "mantissa3_weights"
#: probe seeds beside the configuration's own
MORE_SEEDS = (11, 20260517, 3000000019)
#: ``rows_by_gap``: the sizes of the least gap the rows are split at
GAPS = (0.003, 0.01, 0.03)


def _controls(cfg):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.granite_h import GraniteHBody

    class Mantissa3KV(GraniteHBody):
        def _qkv(self, u, layer):
            q, k, v = super()._qkv(u, layer)
            return q, _to_mantissa3(k), _to_mantissa3(v)

    class Mantissa3State(GraniteHBody):
        """The pool of SSD states rounded after every step that wrote it."""

        def decode(self, *args):
            hidden, (k, v, conv, ssd, counts) = super().decode(*args)
            return hidden, (k, v, conv, _to_mantissa3(ssd), counts)

        def chunk(self, *args):
            hidden, (k, v, conv, ssd, counts) = super().chunk(*args)
            return hidden, (k, v, conv, _to_mantissa3(ssd), counts)

    class Bf16Router(GraniteHBody):
        def _expert_mlp(self, *args):
            def narrow(x32, kernel, top_k):
                z = jnp.dot(x32.astype(jnp.bfloat16), kernel.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
                picked, chosen = jax.lax.top_k(z, top_k)
                return chosen, jax.nn.softmax(picked, axis=-1)

            with mock.patch("ray_tpu.models.granite_h.route_logits", narrow):
                return super()._expert_mlp(*args)

    def with_body(body):
        class Config(type(cfg)):
            def serving_body(self):
                return body(self)

        return Config(**dataclasses.asdict(cfg))

    return {
        "configured": cfg,
        "mantissa3_ssd_state": with_body(Mantissa3State),
        "mantissa3_kv": with_body(Mantissa3KV),
        "bf16_router": with_body(Bf16Router),
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
        self.step = jax.jit(self.runner._decode_logits)

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
            arrays = (*pool.arrays, *runner._counts)
            out, got = [], []
            for i in range(want):
                row = np.asarray(logits, np.float32)
                got.append(row)
                out.append(int(row.argmax()))
                if i + 1 == want:
                    break
                tokens, positions = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
                tokens[1], positions[1] = out[-1], len(prompt) + i
                arrays, batch = self.step(runner.params, arrays, tokens, positions, tables)
                logits = batch[1]
            pool.arrays = arrays[:len(pool.arrays)]
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

    from benchmark.reference import granite_h as reference
    from ray_tpu.serve.llm import _seeded_params

    config = H.load_config(H.manifest(), CONFIG)
    sizes = H.sizes(config, args.rehearsal)
    cfg = H.family_piece(config, "model_config")(sizes)
    reference_logits = H.family_piece(config, "reference_logits")
    consts = H.family_piece(config, "reference_sizes")(cfg)
    init = H.family_piece(config, "program_init")()
    seed = config["deployment"]["weights_seed"]
    params = _seeded_params(init, cfg, seed, 1)
    probes = serving.probe_prompts(config, cfg.vocab_size, args.rehearsal)
    tol = sizes["correctness"]["logit_tolerance"]
    report = {"platform": jax.devices()[0].platform, "tolerance": tol,
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

    controls = _controls(cfg)
    names = args.only.split(",") if args.only else list(controls) + [MANTISSA3]

    def judge(name, these, outs, rows):
        per_probe, distance, deficits = [], [], []
        for (seq, at), out, got in zip(_sequences(these, outs), outs, rows):
            want = np.asarray(reference_logits(params, seq, at, cfg))
            deficits.append(_deficits(want, out))
            per_probe.append(float(deficits[-1].max()))
            if got is not None:
                distance.append(got - want)
        worst = max(per_probe)
        line = {"max_deficit": worst, "per_probe_max": per_probe, "ok": bool(worst <= tol),
                "deficit_rms": float(np.sqrt((np.concatenate(deficits) ** 2).mean()))}
        if distance:
            err = np.concatenate(distance)
            line["logit_rows"] = {"rms": float(np.sqrt((err**2).mean())),
                                  "max_abs": float(np.abs(err).max())}
        report["controls"][name] = line
        H.emit("control", name=name, **line)
        save()
        return deficits

    def routing(seq, at):
        """(held experts chosen (layers, rows, held), gap and weight (layers,
        rows)) of the reference at ``highest``, at the output rows."""
        _, masks, gaps, weights = reference.forward(params, seq, consts)
        rows = np.asarray(at)
        return (np.stack([np.asarray(m) for m in masks])[:, rows],
                np.stack([np.asarray(g) for g in gaps])[:, rows],
                np.stack([np.asarray(w) for w in weights])[:, rows])

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
    judged = {name: judge(name, *run) for name, run in served.items()}

    if "configured" in served:  # tells something on a chip only
        per_probe, distance, flipped_d, steady_d, at_flips, by_gap = [], [], [], [], [], []
        for (seq, at), mine in zip(_sequences(probes, served["configured"][1]),
                                   judged["configured"]):
            want = np.asarray(reference_logits(params, seq, at, cfg))
            theirs, gap, weight = routing(seq, at)
            with mock.patch.object(jax, "default_matmul_precision",
                                   lambda _, real=jax.default_matmul_precision: real("bfloat16")):
                got = np.asarray(reference_logits(params, seq, at, cfg))
                narrow, _, _ = routing(seq, at)
            deficit = _deficits(want, got.argmax(axis=-1))
            differ = (narrow != theirs).any(axis=-1)                      # (layers, rows)
            flipped = differ.any(axis=0)
            per_probe.append(float(deficit.max()))
            distance.append(got - want)
            flipped_d.append(deficit[flipped])
            steady_d.append(deficit[~flipped])
            at_flips += [(float(g), float(w)) for g, w in zip(gap[differ], weight[differ])]
            by_gap.append((gap.min(axis=0), mine))
        err = np.concatenate(distance)
        flipped_d, steady_d = np.concatenate(flipped_d), np.concatenate(steady_d)
        least, mine = (np.concatenate(x) for x in zip(*by_gap))
        biggest = lambda d: float(d.max()) if d.size else None  # noqa: E731
        line = {"max_deficit": max(per_probe), "per_probe_max": per_probe,
                "logit_rows": {"rms": float(np.sqrt((err**2).mean())),
                               "max_abs": float(np.abs(err).max())},
                "flips": {"rows_flipped": int(flipped_d.size), "rows_steady": int(steady_d.size),
                          "layer_rows_flipped": len(at_flips),
                          "max_deficit_flipped": biggest(flipped_d),
                          "max_deficit_steady": biggest(steady_d),
                          "largest_finite_gap_at_a_flip": max(
                              (g for g, _ in at_flips if g < float("inf")), default=None),
                          "largest_weight_at_a_flip": max((w for _, w in at_flips), default=None)},
                "rows_by_gap": {str(g): {"rows_under": int((least < g).sum()),
                                         "max_deficit_under": biggest(mine[least < g]),
                                         "max_deficit_over": biggest(mine[least >= g])}
                                for g in GAPS}}
        report["witness"] = line
        H.emit("witness", **line)
        save()

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


if __name__ == "__main__":
    main()
