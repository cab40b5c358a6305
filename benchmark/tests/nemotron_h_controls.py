"""Controls for ``nemotron-3-nano-30b-ep8-1chip``'s ``logit_tolerance`` and for
what a flipped routing choice costs: what the reference comparison reads on
the configured programs over four probe seeds, on programs held one precision
lower in ONE place each, and on the plain reference itself with its products'
inputs rounded to bfloat16.

    python3 benchmark/tests/nemotron_h_controls.py [--rehearsal] [--out FILE] [--only a,b]

One process, on the chip at the published widths and ALL 52 layers
(``--rehearsal``: the configuration's tiny sizes on a CPU, to try the
script).  The served programs (``HybridModelRunner``'s prefill chunk and
decode at the engine's ``prefill_chunk``, block size and table:
``granite_h_controls._Served``, its decode donating the pools) answer the
configuration's probe prompts greedily, one probe at a time in row 1 of a
full decode batch; the statistic is ``reference_check``'s: at each output position the reference's logit of the
program's token against the reference's largest, through the family's
``reference_logits`` (EVERY row: the configuration's routing margin is 0).  A
departure is planted HERE, by overriding one method of the body or rounding
the weights it is given: the served programs hold no such switch.

* ``configured``: what the cell serves, on the configuration's probe seed.
  Every control on that seed also gives the program's whole logit rows
  against the reference's (rms, largest).  ``configured_seed<n>``: the same
  programs on three more probe seeds.
* ``mantissa3_ssd_state``: the pool of SSD states rounded to 3 bits of
  mantissa (float8_e4m3's) after every step that wrote it.  Must fail.
* ``mantissa3_kv``: every key and value rounded to 3 bits of mantissa on its
  way into the K/V pool.  Must fail.
* ``mantissa3_routed_experts``: the ROUTED experts' matrices alone rounded to
  3 bits of mantissa (the reference keeps the weights as they are).  The
  logits do not hear them (a routed expert adds 0.04 of a unit:
  ``expert_init``); it must fail the family's ``expert_layer_deviation``, what
  every run of the cell checks in its reference step: the ``expert_layer_probe``
  lines, on the configured run's four sequences, for the seeded weights (must
  pass ``EXPERT_LAYER_TOLERANCE``) and for the rounded ones (EVERY layer with
  a held pair, in both forms, must fail it).
* ``mantissa3_shared_expert``: the shared expert's matrices alone (the same
  ungated form, through ``ops.moe.relu2``, plain XLA) at 3 bits.  Must fail
  the logits' limit.
* ``witness`` (says something on a chip only): the plain reference ITSELF
  with its matrix products at the chip's default precision (operands rounded
  to bfloat16, float32 sums; no cache, no chunk, no kernel) against itself at
  ``highest``, on the configured run's sequences: its deficits, and the
  ROUTING FLIPS between the two (positions where some layer's set of chosen
  held experts differs), with the deficits of the rows that flipped beside
  those of the rows that did not.  ``rows_by_gap`` on the configured run: the
  largest deficit among the rows whose least ``gap`` over the layers lies
  under each of a few sizes, and among the rest.
* ``routing_load``: what the rows of a decode batch choose, on 16 seeded
  sequences' last tokens, an expert layer at a time: how many of the 128
  experts and of the 16 held ones the 16 rows touch beside what independent
  uniform choices would (68.6 and 8.6), the rows' most chosen experts, how
  alike the rows' normed inputs are (mean pairwise cosine), and the same
  counts with the rows' common part taken out of their inputs.
* ``kernels`` (a chip only): both expert kernels in their ungated form at the
  published 2,688 x 1,856 (stored 1,920) against the plain ``jax.numpy`` forms
  on the same pairs: the relative rms between them.
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

CONFIG = "nemotron-3-nano-30b-ep8-1chip"
#: the weight controls: which matrices are rounded
WEIGHTS = {"mantissa3_shared_expert": ("shared",), "mantissa3_routed_experts": ("experts",)}
#: ``routing_load``: the rows of a decode batch, and each row's context
LOAD_ROWS, LOAD_CONTEXT = 16, 1024
#: probe seeds beside the configuration's own
MORE_SEEDS = (11, 20260517, 3000000019)
#: ``rows_by_gap``: the sizes of the least gap (in selection score) the rows are split at
GAPS = (0.001, 0.003, 0.01)


def _controls(cfg):
    from ray_tpu.models.nemotron_h import NemotronHBody

    class Mantissa3KV(NemotronHBody):
        def _qkv(self, u, layer):
            q, k, v = super()._qkv(u, layer)
            return q, _to_mantissa3(k), _to_mantissa3(v)

    class Mantissa3State(NemotronHBody):
        """The pool of SSD states rounded after every step that wrote it."""

        def decode(self, *args):
            hidden, (k, v, conv, ssd, counts) = super().decode(*args)
            return hidden, (k, v, conv, _to_mantissa3(ssd), counts)

        def chunk(self, *args):
            hidden, (k, v, conv, ssd, counts) = super().chunk(*args)
            return hidden, (k, v, conv, _to_mantissa3(ssd), counts)

    def with_body(body):
        class Config(type(cfg)):
            def serving_body(self):
                return body(self)

        return Config(**{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)})

    return {
        "configured": cfg,
        "mantissa3_ssd_state": with_body(Mantissa3State),
        "mantissa3_kv": with_body(Mantissa3KV),
    }


class _Tight(_Served):
    """``_Served`` whose decode DONATES the pools and the ledger, as the
    engine's own step does: at 12.0 GB of weights a second copy of the pools
    (1.77 GB a step's results) does not fit beside the first."""

    def __init__(self, cfg, params, engine: dict):
        import jax

        super().__init__(cfg, params, engine)
        decode = jax.jit(self.runner._decode_logits, donate_argnums=(1,))

        def step(params, arrays, *rest):
            arrays, logits = decode(params, arrays, *rest)
            # the donated ledger's successor, for the next probe's first step
            self.runner._counts = arrays[len(self.pool.arrays):]
            return arrays, logits

        self.step = step


def _round_experts(params: dict, which: tuple) -> dict:
    """``params`` with the routed experts' matrices (``experts``) and / or every
    expert layer's shared expert's (``shared``) at 3 bits of mantissa."""
    out = dict(params)
    if "experts" in which:
        out["experts"] = _to_mantissa3(params["experts"])
    if "shared" in which:
        out["runs"] = [dict(run, shared=_to_mantissa3(run["shared"])) if "shared" in run else run
                       for run in params["runs"]]
    return out


def _kernels(cfg, params) -> dict:
    """Both expert kernels against the plain forms at the published widths, on
    the first expert layer's held experts and seeded rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import moe

    out = {}
    for name, n in (("batch", 16), ("grouped", 512)):
        ks = jax.random.split(jax.random.PRNGKey(n), 2)
        x = jax.random.normal(ks[0], (n, cfg.d_model), jnp.float32)
        router = jax.random.normal(ks[1], (cfg.d_model, cfg.n_routed_experts)) * cfg.d_model**-0.5
        chosen, weights = moe.route(x, router, jnp.zeros(cfg.n_routed_experts),
                                    cfg.experts_per_tok, cfg.routed_scaling)
        mask, wmat = moe.held_pairs(chosen, weights, cfg.expert_offset, cfg.experts_held,
                                    jnp.ones(n, bool))
        form = lambda impl: np.asarray(jax.jit(lambda x, m, w, u, d: moe.expert_layer(  # noqa: E731
            x, m, w, u, d, first=cfg.experts_held, top_k=cfg.experts_per_tok, impl=impl))(
            x.astype(cfg.dtype), mask, wmat, params["experts"]["up"], params["experts"]["down"]))
        kernel, plain = form("pallas"), form("xla")
        out[name] = {"relative_rms": float(np.linalg.norm(kernel - plain)
                                           / max(np.linalg.norm(plain), 1e-30)),
                     "pairs": int(mask.sum()), "touched": int(mask.any(axis=0).sum()),
                     "rms": float(np.sqrt((plain**2).mean()))}
    return out


def _routing_load(params, consts: dict, cfg, context: int) -> dict:
    """The module's note: ``LOAD_ROWS`` seeded sequences of ``context`` tokens,
    the reference's stream at each one's last token, routed a layer at a
    time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import nemotron_h as reference

    rng = np.random.default_rng(20261004)
    taps = []
    for _ in range(LOAD_ROWS):
        tap = {"rows": np.array([context - 1])}
        reference.forward(params, rng.integers(1, cfg.vocab_size, context), consts, tap)
        taps.append(tap["layers"])
    routers = [jax.tree_util.tree_map(lambda a, i=i: a[i], run["router"])
               for run in params["runs"] if "router" in run
               for i in range(run["ln2"]["scale"].shape[0])]  # the norms' scales are 1
    held = np.arange(cfg.expert_offset, cfg.expert_offset + cfg.experts_held)
    layers = []
    for index, router in enumerate(routers):
        h = np.concatenate([t[index][0] for t in taps])                   # (rows, d)
        y = h / np.sqrt((h * h).mean(-1, keepdims=True) + cfg.norm_eps)
        line = {"layer": index}
        for name, rows in (("", y), ("_centred", y - y.mean(axis=0))):
            with jax.default_matmul_precision("highest"):
                chosen = np.asarray(reference.route(jnp.asarray(rows), router, consts)[0])
            counts = np.bincount(chosen.ravel(), minlength=cfg.n_routed_experts)
            line.update({f"distinct{name}": int((counts > 0).sum()),
                         f"held_pairs{name}": int(counts[held].sum()),
                         f"held_touched{name}": int((counts[held] > 0).sum())})
            if not name:
                line["most_chosen"] = sorted(counts.tolist(), reverse=True)[:8]
                line["held_counts"] = counts[held].tolist()
        unit = y / np.linalg.norm(y, axis=-1, keepdims=True)
        cos = unit @ unit.T
        line["mean_cosine"] = float((cos.sum() - len(y)) / (len(y) * (len(y) - 1)))
        layers.append(line)
    mean = lambda k: float(np.mean([x[k] for x in layers]))  # noqa: E731
    wide, k = cfg.n_routed_experts, cfg.experts_per_tok
    return {"rows": LOAD_ROWS, "context": context,
            "uniform": {"distinct": wide * (1 - (1 - k / wide)**LOAD_ROWS),
                        "held_pairs": LOAD_ROWS * k * cfg.experts_held / wide,
                        "held_touched": cfg.experts_held * (1 - (1 - k / wide)**LOAD_ROWS)},
            "mean": {key: mean(key) for key in layers[0] if isinstance(layers[0][key], (int, float))
                     and key != "layer"},
            "layers": layers}


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

    from benchmark.reference import nemotron_h as reference
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
    names = args.only.split(",") if args.only else [
        "kernels", "routing_load", *controls, "witness", *WEIGHTS]
    family, tapped = H.load_family(config), []

    def layer_probe(name, program_params):
        """The family's expert-layer probe of the configured programs over
        ``program_params``, on the streams the reference (the seeded weights)
        has on the configured run's sequences (``tapped``)."""
        probed = [family.expert_layer_deviation(cfg, program_params, taps, chunk)
                  for taps, chunk in tapped]
        each = [[x[form] for x in layers for form in ("chunk", "decode")] for layers in probed]
        # a layer no probe row has a held pair in reads 0 in both: not a reading
        line = {"largest": max(map(max, each)),
                "smallest_above_0": min(e for per in each for e in per if e > 0),
                "per_probe_largest": [max(per) for per in each],
                "tolerance": family.EXPERT_LAYER_TOLERANCE, "layers": probed}
        line["ok"] = bool(line["largest"] <= family.EXPERT_LAYER_TOLERANCE)
        report.setdefault("expert_layer_probe", {})[name] = line
        H.emit("expert_layer_probe_of", name=name, **{k: v for k, v in line.items()
                                                      if k != "layers"})
        save()

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
        """(held experts chosen (layers, rows, held), gap (layers, rows)) of
        the reference at the precision in force, at the output rows."""
        _, gaps, masks = reference.forward(params, seq, consts)
        rows = np.asarray(at)
        return (np.stack([np.asarray(m) for m in masks])[:, rows],
                np.stack([np.asarray(g) for g in gaps])[:, rows])

    if "kernels" in names and not args.rehearsal:
        report["kernels"] = _kernels(cfg, params)
        H.emit("kernels", **report["kernels"])
        save()
    if "routing_load" in names:
        report["routing_load"] = _routing_load(
            params, consts, cfg, 40 if args.rehearsal else LOAD_CONTEXT)
        H.emit("routing_load", **{k: v for k, v in report["routing_load"].items()
                                  if k != "layers"})
        save()

    served = {}
    for name in names:
        if name not in controls:
            continue
        programs = _Tight(controls[name], params, sizes["engine"])
        served[name] = (probes, *programs.probes(probes, keep_logits=True))
        if name == "configured":
            for s in MORE_SEEDS:
                more = probes_of(s)
                served[f"configured_seed{s}"] = (more, *programs.probes(more, False))
        del programs
        gc.collect()
        H.note(f"{name}: served")
    judged = {name: judge(name, *run) for name, run in served.items()}
    if "configured" in served:
        for seq, at in _sequences(probes, served["configured"][1]):
            rows, chunk = family.probe_rows(min(at), max(at))
            tapped.append(({"rows": rows}, chunk))
            reference.forward(params, seq, consts, tapped[-1][0])
        layer_probe("configured", params)

    if "witness" in names and "configured" in served:  # tells something on a chip only
        per_probe, distance, flipped_d, steady_d, at_flips, by_gap = [], [], [], [], [], []
        for (seq, at), mine in zip(_sequences(probes, served["configured"][1]),
                                   judged["configured"]):
            want = np.asarray(reference_logits(params, seq, at, cfg))
            theirs, gap = routing(seq, at)
            with mock.patch.object(jax, "default_matmul_precision",
                                   lambda _, real=jax.default_matmul_precision: real("bfloat16")):
                # the reference's own logits: the family's expert-layer probe
                # is for a sound reference, not for this one
                got = np.asarray(reference.logits_at(params, seq, at, consts))
                narrow, _ = routing(seq, at)
            deficit = _deficits(want, got.argmax(axis=-1))
            differ = (narrow != theirs).any(axis=-1)                      # (layers, rows)
            flipped = differ.any(axis=0)
            per_probe.append(float(deficit.max()))
            distance.append(got - want)
            flipped_d.append(deficit[flipped])
            steady_d.append(deficit[~flipped])
            at_flips += [float(g) for g in gap[differ]]
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
                              (g for g in at_flips if g < float("inf")), default=None)},
                "rows_by_gap": {str(g): {"rows_under": int((least < g).sum()),
                                         "max_deficit_under": biggest(mine[least < g]),
                                         "max_deficit_over": biggest(mine[least >= g])}
                                for g in GAPS}}
        report["witness"] = line
        H.emit("witness", **line)
        save()

    for name, which in WEIGHTS.items():
        if name not in names:
            continue
        # last: the matrices are rounded where they lie (two trees do not fit
        # the chip), served, and made anew from the seed for the reference
        rounded = jax.jit(lambda p, which=which: _round_experts(p, which),
                          donate_argnums=0)(params)
        del params
        programs = _Tight(cfg, rounded, sizes["engine"])
        outs, rows = programs.probes(probes, keep_logits=True)
        if tapped:
            layer_probe(name, rounded)
        del rounded, programs
        gc.collect()
        params = _seeded_params(init, cfg, seed, 1)
        judge(name, probes, outs, rows)


if __name__ == "__main__":
    main()
