"""Controls for ``lfm2-24b-a2b-l10-1chip``'s ``logit_tolerance`` and
``routing_margin``: what the reference comparison reads on the configured
programs over four probe seeds, on programs held one precision lower in ONE
place each, and on the plain reference itself with its products' inputs
rounded to bfloat16.

    python3 benchmark/tests/lfm2_controls.py [--rehearsal] [--out FILE] [--only a,b]

One process, on the chip at the published widths (``--rehearsal``: the
configuration's tiny sizes on a CPU, to try the script).  The served programs
(``HybridModelRunner``'s prefill chunk and decode at the engine's
``prefill_chunk``, block size and table) answer the configuration's probe
prompts greedily, one probe at a time in row 1 of a full decode batch; the
statistic is ``reference_check``'s: at each output position the reference's
logit of the program's token against the reference's largest.  EVERY row is
read, beside its routing margin (``reference.logits_and_margins``), so that
one run gives the reading at any margin: ``by_margin`` has, for each of
``MARGINS``, the rows it leaves and the largest deficit among them; the line's
``max_deficit`` / ``ok`` are at the file's own ``ROUTING_MARGIN``, what
``reference_check`` reads.  A departure is planted HERE, by overriding one
method of the body: the served programs hold no such switch.

Beside the logits, the family's EXPERT-LAYER PROBE (``families/lfm2_moe.py::
expert_layer_deviation``, what every run of the cell checks in its reference
step): the program's expert layer in both forms against the reference's loop
layer by layer on the reference's own stream, for the configured programs
(must pass ``EXPERT_LAYER_TOLERANCE``) and for ``experts_shifted``,
``mantissa3_experts`` and ``mantissa3_weights`` (each must FAIL it): the
``expert_layer_probe`` lines.

* ``configured``: what the cell serves, on the configuration's probe seed.
  Every control on that seed also gives the program's whole logit rows
  against the reference's (rms, largest).  ``configured_seed<n>``: the same
  programs on three more probe seeds.
* ``mantissa3_tails``: the pool of the convolutions' tails rounded to 3 bits
  of mantissa (float8_e4m3's) after every step that wrote it.  Must fail.
* ``mantissa3_kv``: every key and value rounded to 3 bits of mantissa on its
  way into the K/V pool.  Must fail.
* ``mantissa3_weights``: every weight matrix rounded to 3 bits of mantissa
  (the reference keeps the weights as they are).  Must fail.
* ``experts_shifted``: every chosen pair goes through the NEXT expert's
  weights (expert ``e + 1`` of the layer's, at ``e``'s weight): what a kernel
  that read another block than the router named would do.  A planted fault,
  not a lower precision.  Must fail.
* ``mantissa3_experts``: the experts' matrices ALONE at 3 bits of mantissa.
  In the LOGITS reported as found: it reads under the limit at every
  initializer tried, because the rounding of the two leading layers'
  convolutions, amplified by the eight layers after them, is four fifths of
  the noise in the logits and the experts' products a twentieth
  (``lfm2_floor.py`` reads it).  Must fail the expert-layer probe, below.
* ``bf16_router``: the router's input and product in bfloat16 (one pass), as
  the published module runs it.  Not a precision below the configured path,
  whose every other product already rounds its inputs to bfloat16: reported
  as found.
* ``witness`` (says something on a chip only): the plain reference ITSELF
  with its matrix products at the chip's default precision (operands rounded
  to bfloat16, float32 sums; no cache, no chunk, no kernel, no tiles) against
  itself at ``highest``, on the configured run's sequences: its deficits by
  margin, and the ROUTING FLIPS between the two (rows where some expert
  layer's set of chosen experts differs) with the largest margin at a flip.
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
from benchmark.tests.granite_h_controls import _Served  # noqa: E402
from benchmark.tests.kimi_controls import _to_mantissa3  # noqa: E402
from benchmark.tests.phi4flash_controls import _deficits  # noqa: E402

CONFIG = "lfm2-24b-a2b-l10-1chip"
MANTISSA3, EXPERTS3 = "mantissa3_weights", "mantissa3_experts"
#: probe seeds beside the configuration's own
MORE_SEEDS = (11, 20260517, 3000000019)
#: the margins ``by_margin`` reads at, in units of the selection score
MARGINS = (0.0, 0.001, 0.002, 0.003, 0.004, 0.006, 0.008, 0.012, 0.016)


def _controls(cfg):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.lfm2 import Lfm2MoeBody

    class Mantissa3KV(Lfm2MoeBody):
        def _packed(self, x):
            return super()._packed(_to_mantissa3(x.astype(self.dt)))

    class Mantissa3Tails(Lfm2MoeBody):
        """The pool of tails rounded after every step that wrote it."""

        def decode(self, *args):
            hidden, (k, v, tails, counts) = super().decode(*args)
            return hidden, (k, v, _to_mantissa3(tails), counts)

        def chunk(self, *args):
            hidden, (k, v, tails, counts) = super().chunk(*args)
            return hidden, (k, v, _to_mantissa3(tails), counts)

    class ExpertsShifted(Lfm2MoeBody):
        def _expert_mlp(self, *args):
            from ray_tpu.ops.moe import held_pairs

            def shifted(*a):
                return tuple(jnp.roll(x, 1, axis=1) for x in held_pairs(*a))

            with mock.patch("ray_tpu.models.lfm2.held_pairs", shifted):
                return super()._expert_mlp(*args)

    class Bf16Router(Lfm2MoeBody):
        def _expert_mlp(self, *args):
            def narrow(x32, kernel, bias, top_k, scaling, eps):
                p = jax.nn.sigmoid(jnp.dot(
                    x32.astype(jnp.bfloat16), kernel.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32))
                _, chosen = jax.lax.top_k(p + bias, top_k)
                picked = jnp.take_along_axis(p, chosen, axis=-1)
                return chosen, picked / (picked.sum(-1, keepdims=True) + eps) * scaling

            with mock.patch("ray_tpu.models.lfm2.route", narrow):
                return super()._expert_mlp(*args)

    def with_body(body):
        class Config(type(cfg)):
            def serving_body(self):
                return body(self)

        return Config(**dataclasses.asdict(cfg))

    return {
        "configured": cfg,
        "mantissa3_tails": with_body(Mantissa3Tails),
        "mantissa3_kv": with_body(Mantissa3KV),
        "experts_shifted": with_body(ExpertsShifted),
        "bf16_router": with_body(Bf16Router),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--only", default=None, help="comma-separated controls")
    ap.add_argument("--set", action="append", default=[], metavar="GROUP.KEY=JSON",
                    help="another initializer to read (a trial, not the configuration)")
    args = ap.parse_args()
    H.prepare_environment(args.rehearsal)
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import numpy as np

    from benchmark.reference import lfm2_moe as reference
    from ray_tpu.serve.llm import _seeded_params

    config = H.load_config(H.manifest(), CONFIG)
    sizes = H.sizes(config, args.rehearsal)
    for item in args.set:
        where, _, value = item.partition("=")
        group, key = where.split(".")
        sizes = dict(sizes, **{group: dict(sizes[group], **{key: json.loads(value)})})
    cfg = H.family_piece(config, "model_config")(sizes)
    consts = H.family_piece(config, "reference_sizes")(cfg)
    init = H.family_piece(config, "program_init")()
    seed = config["deployment"]["weights_seed"]
    params = _seeded_params(init, cfg, seed, 1)
    probes = serving.probe_prompts(config, cfg.vocab_size, args.rehearsal)
    tol = sizes["correctness"]["logit_tolerance"]
    own = reference.ROUTING_MARGIN
    report = {"platform": jax.devices()[0].platform, "tolerance": tol, "routing_margin": own,
              "set": args.set,
              "prompt_lens": [len(p["prompt"]) for p in probes], "controls": {}, "rows": {}}

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
    names = (args.only.split(",") if args.only
             else list(controls) + ["witness", EXPERTS3, MANTISSA3])

    def by_margin(deficit, margin) -> dict:
        return {str(m): {"rows": int((margin >= m).sum()),
                         "max_deficit": float(deficit[margin >= m].max(initial=0.0))}
                for m in MARGINS}

    def read(name, deficit, margin, probe_of, **more):
        kept = margin >= own
        per_probe = [float(deficit[kept & (probe_of == i)].max(initial=0.0))
                     for i in range(len(probes))]
        line = {"max_deficit": max(per_probe), "per_probe_max": per_probe,
                "ok": bool(max(per_probe) <= tol), "rows": int(kept.sum()),
                "deficit_rms": float(np.sqrt((deficit[kept] ** 2).mean())) if kept.any() else 0.0,
                "by_margin": by_margin(deficit, margin), **more}
        report["controls"][name] = line
        report["rows"][name] = {"deficit": [float(x) for x in deficit],
                                "margin": [float(x) for x in margin]}
        H.emit("control", name=name, **line)
        save()

    def judge(name, these, outs, rows):
        deficits, margins, probe_of, distance = [], [], [], []
        for i, ((seq, at), out, got) in enumerate(zip(_sequences(these, outs), outs, rows)):
            want, margin = (np.asarray(x) for x in reference.logits_and_margins(
                params, seq, at, consts))
            deficits.append(_deficits(want, out))
            margins.append(margin)
            probe_of.append(np.full(len(out), i))
            if got is not None:
                distance.append(got - want)
        more = {}
        if distance:
            err = np.concatenate(distance)
            more["logit_rows"] = {"rms": float(np.sqrt((err**2).mean())),
                                  "max_abs": float(np.abs(err).max())}
        read(name, np.concatenate(deficits), np.concatenate(margins), np.concatenate(probe_of),
             **more)

    family, probe = H.load_family(config), {}

    def layer_probe(name, model, program_params):
        """The family's expert-layer probe of ``model``'s programs over
        ``program_params``, on the stream the reference (the seeded weights)
        has on the configured run's longest sequence."""
        if not probe:
            seq, at = list(_sequences(probes, served["configured"][1]))[-1]
            rows, probe["chunk"] = family.probe_rows(min(at), max(at))
            probe["taps"] = {"rows": rows}
            reference.forward(params, seq, consts, probe["taps"])
        layers = family.expert_layer_deviation(
            model, program_params, probe["taps"], probe["chunk"])
        each = [x[form] for x in layers for form in ("chunk", "decode")]
        line = {"largest": max(each), "smallest": min(each),
                "tolerance": family.EXPERT_LAYER_TOLERANCE,
                "ok": bool(max(each) <= family.EXPERT_LAYER_TOLERANCE), "layers": layers}
        report.setdefault("expert_layer_probe", {})[name] = line
        H.emit("expert_layer_probe", name=name, **line)
        save()

    served = {}
    for name in names:
        if name not in controls:
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
    for name, run in served.items():
        judge(name, *run)
    for name in ("configured", "experts_shifted"):
        if name in served:
            layer_probe(name, controls[name], params)

    if "configured" in served and "witness" in names:  # tells something on a chip only
        deficits, margins, probe_of, flipped, distance = [], [], [], [], []
        for i, (seq, at) in enumerate(_sequences(probes, served["configured"][1])):
            rows = np.asarray(at)
            want, margin = (np.asarray(x) for x in reference.logits_and_margins(
                params, seq, at, consts))
            _, theirs, _ = reference.forward(params, seq, consts)
            with mock.patch.object(jax, "default_matmul_precision",
                                   lambda _, real=jax.default_matmul_precision: real("bfloat16")):
                got = np.asarray(reference.logits_and_margins(params, seq, at, consts)[0])
                _, narrow, _ = reference.forward(params, seq, consts)
            differ = np.stack([np.asarray(a)[rows] != np.asarray(b)[rows]
                               for a, b in zip(narrow, theirs)]).any(axis=(0, 2))
            deficits.append(_deficits(want, got.argmax(axis=-1)))
            margins.append(margin)
            probe_of.append(np.full(len(at), i))
            flipped.append(differ)
            distance.append(got - want)
        err, flipped = np.concatenate(distance), np.concatenate(flipped)
        deficit, margin = np.concatenate(deficits), np.concatenate(margins)
        biggest = lambda d: float(d.max()) if d.size else None  # noqa: E731
        read("witness", deficit, margin, np.concatenate(probe_of),
             logit_rows={"rms": float(np.sqrt((err**2).mean())),
                         "max_abs": float(np.abs(err).max())},
             flips={"rows_flipped": int(flipped.sum()), "rows_steady": int((~flipped).sum()),
                    "max_deficit_flipped": biggest(deficit[flipped]),
                    "max_deficit_steady": biggest(deficit[~flipped]),
                    "largest_margin_at_a_flip": biggest(margin[flipped])})

    # last: the weights are rounded where they lie (two trees do not fit the
    # chip), served, and made anew from the seed for the reference: the
    # experts alone first, then every matrix
    for name, part in ((EXPERTS3, lambda p: dict(p, experts=_to_mantissa3(p["experts"]))),
                       (MANTISSA3, _to_mantissa3)):
        if name not in names:
            continue
        if "configured" in served and not probe:
            layer_probe("configured", cfg, params)  # the taps need the seeded weights
        rounded = jax.jit(part, donate_argnums=0)(params)
        del params
        programs = _Served(cfg, rounded, sizes["engine"])
        outs, rows = programs.probes(probes, keep_logits=True)
        if probe:
            layer_probe(name, cfg, rounded)
        del rounded, programs
        gc.collect()
        params = _seeded_params(init, cfg, seed, 1)
        judge(name, probes, outs, rows)


if __name__ == "__main__":
    main()
