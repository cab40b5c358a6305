"""Controls for ``kimi-k2.5-ep32-l7-1chip``'s ``logit_tolerance`` and
``routing_margin``: what the HARNESS's comparison reads on the configured
programs, on programs held one precision lower, and what bf16 rounding ALONE
reads in code that shares nothing with the program; and the ROUTING FLIPS:
top-8 of 384 is discontinuous, so where a held expert's score lies closer to
the boundary than the bf16 products upstream move it, the program and the
reference choose differently.

    python3 benchmark/tests/kimi_controls.py [--rehearsal] [--out FILE] [--only a,b]
        [--probe-seeds n,m] [--margins x,y]

One process, on the chip at the published widths (``--rehearsal``: the
configuration's tiny sizes on a CPU, to try the script).  The served
programs (``HybridModelRunner``'s prefill chunk and decode, the engine's
``prefill_chunk``, block size and table) answer the configuration's probe
prompts greedily, one probe at a time in row 1 of a full decode batch.  A
departure is planted HERE, by overriding one method of the body: the served
programs hold no such switch.

**The verdict is the harness's**: ``reference_check.main`` is called as it
is on a job of the probes and the tokens served (its weights and the
reference's forward pass are handed over from this process, nothing else is
touched), under the reference's ``ROUTING_MARGIN`` and under each of
``--margins``.  Beside it, for every run, the file ``--out`` keeps what no
verdict shows: every row's deficit with NOTHING masked, every row's routing
margin, and the flips with the reference's margin at each.

* ``configured``: what the cell serves, with every expert layer's choice of
  held experts recorded at every token it was fed (a host callback from the
  layer: the recorded run IS the configured program, its products
  untouched).  Also the program's whole logit rows against the reference's
  (rms, largest).  ``--probe-seeds``: the same on other probe prompts (what
  a later PR that re-draws the flips may read).
* ``bf16_router``: the router's input and product in bfloat16 (one pass).
* ``bf16_stream``: the residual stream rounded to bfloat16 at every layer's
  end.  Neither is a precision BELOW the configured path (every product
  already rounds its inputs to bfloat16); both read as it does.
* ``e4m3_latent_rows``: a token's cache row rounded to float8_e4m3's 3 bits of
  mantissa as it is written (the pool stays bfloat16).  Must fail.
* ``mantissa3_weights``: every weight matrix rounded to float8_e4m3's 3 bits
  of mantissa where it lies (the reference keeps the weights as they are):
  the precision below bfloat16 for the ONE operand every product has.  Must
  fail.
* ``witness`` (says something on a chip only): the plain reference ITSELF
  with its matrix products at the chip's default precision (operands rounded
  to bfloat16, float32 sums; no cache, no kernel, no absorbed form) against
  itself at ``highest``, on the configured run's sequences.  Its tokens are
  each row's own largest and not one greedy sequence, so its rows are judged
  here, by the harness's expression under the same margin.
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

CONFIG = "kimi-k2.5-ep32-l7-1chip"
MANTISSA3 = "mantissa3_weights"
#: (layer, mask of the live rows) as the recording body's layers report them
RECORDED: list = []


def _to_mantissa3(params):
    """Every matrix rounded to 3 bits of mantissa, float8_e4m3's, where
    bfloat16 has 7; the exponent stays.  By its bits (``phi4flash_controls``
    found that a cast to float8 and back reaches a v5e's programs unrounded)."""
    import jax
    import jax.numpy as jnp

    def rounded(w):
        if w.ndim < 2 or w.shape[-1] == 1 or w.shape[-2] == 1:
            return w
        if w.ndim == 2 and w.shape[0] <= 64:  # stacked norm scales and biases
            return w
        uint, drop = {2: (jnp.uint16, 4), 4: (jnp.uint32, 20)}[w.dtype.itemsize]
        bits = jax.lax.bitcast_convert_type(w, uint) + uint(1 << (drop - 1))
        return jax.lax.bitcast_convert_type(bits >> drop << drop, w.dtype)

    return jax.tree.map(rounded, params)


def _controls(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.kimi_k2 import KimiK2Body, _rmsnorm
    from ray_tpu.ops import moe

    class Recorded(KimiK2Body):
        def _expert_mlp(self, h, layer, live, counts, phase, experts=None, index=0):
            c = self.cfg
            chosen, weights = moe.route(
                _rmsnorm(h, layer["ln2"]["scale"], c.rms_norm_eps),
                layer["router"]["kernel"], layer["router"]["bias"],
                c.experts_per_tok, c.routed_scaling_factor)
            mask, _ = moe.held_pairs(chosen, weights, c.expert_offset, c.experts_held, live)
            jax.debug.callback(
                lambda i, m, ok: RECORDED.append((int(i), np.asarray(m)[np.asarray(ok)])),
                index, mask, live)
            return super()._expert_mlp(h, layer, live, counts, phase, experts, index)

    class Bf16Router(KimiK2Body):
        def _expert_mlp(self, h, layer, live, counts, phase, experts=None, index=0):
            def narrow(x32, kernel, bias, top_k, scaling):
                z = jnp.dot(x32.astype(jnp.bfloat16), kernel.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
                p = jax.nn.sigmoid(z)
                _, chosen = jax.lax.top_k(p + bias.astype(jnp.float32), top_k)
                picked = jnp.take_along_axis(p, chosen, axis=-1)
                return chosen, picked / (picked.sum(-1, keepdims=True) + 1e-20) * scaling

            with mock.patch("ray_tpu.models.kimi_k2.route", narrow):
                return super()._expert_mlp(h, layer, live, counts, phase, experts, index)

    class E4m3Rows(KimiK2Body):
        def _project(self, x, layer, positions):
            q_nope, q_rope, row = super()._project(x, layer, positions)
            return q_nope, q_rope, row.astype(jnp.float8_e4m3fn).astype(row.dtype)

    class Bf16Stream(KimiK2Body):
        def _attn_out(self, x, layer, o):
            return super()._attn_out(x, layer, o).astype(jnp.bfloat16).astype(jnp.float32)

    def with_body(body):
        class Config(type(cfg)):
            def serving_body(self):
                return body(self)

        return Config(**dataclasses.asdict(cfg))

    return {
        "configured": with_body(Recorded),
        "bf16_router": with_body(Bf16Router),
        "e4m3_latent_rows": with_body(E4m3Rows),
        "bf16_stream": with_body(Bf16Stream),
    }


def _serve_probes(cfg, params, engine: dict, probes: list, keep_logits: bool):
    """Each probe greedily through the served programs.  Returns (outs,
    logit rows a probe or None, per probe the recorded choices: an array
    (expert layers, tokens fed, held) or None)."""
    import jax
    import numpy as np

    from ray_tpu.llm.cache import CacheConfig, KVBlockPool
    from ray_tpu.llm.model_runner import pack_knobs
    from ray_tpu.llm.state_runner import HybridModelRunner

    bs, chunk, slots = engine["block_size"], engine["prefill_chunk"], engine["max_slots"]
    runner = HybridModelRunner(cfg, params, bs)
    pool = KVBlockPool(CacheConfig(engine["num_blocks"], bs, engine["max_blocks_per_seq"]),
                       **runner.body.kv_layout())
    greedy = pack_knobs(0, 0.0, 0, 1.0, 0)
    step = jax.jit(runner._decode_logits)
    outs, rows, choices = [], [], []
    for n, probe in enumerate(probes):
        prompt, want = probe["prompt"], probe["max_tokens"]
        name = f"probe{n}"
        pool.allocate(name, len(prompt) + want)
        table = pool.table_row(name)
        del RECORDED[:]
        for pos in range(0, len(prompt), chunk):
            piece = prompt[pos:pos + chunk]
            buf = np.zeros(chunk, np.int32)
            buf[:len(piece)] = piece
            *arrays, logits, _, _ = runner.prefill_chunk(
                *pool.arrays, buf, pos, len(piece), table, greedy)
            pool.arrays = arrays
            jax.effects_barrier()
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
            arrays, batch = step(runner.params, arrays, tokens, positions, tables)
            logits = batch[1]
            jax.effects_barrier()
        pool.arrays = arrays[:len(pool.arrays)]
        pool.free(name)
        outs.append(out)
        rows.append(np.stack(got) if keep_logits else None)
        layers = sorted({i for i, _ in RECORDED})
        choices.append(np.stack([
            np.concatenate([m for i, m in RECORDED if i == layer]) for layer in layers
        ]) if RECORDED else None)
    del pool, runner, step, arrays
    gc.collect()
    return outs, rows, choices


def _sequences(probes, outs):
    """As ``reference_check``: prompt and chosen tokens, the output rows."""
    for probe, out in zip(probes, outs):
        prompt = probe["prompt"]
        yield prompt + out[:-1], list(range(len(prompt) - 1, len(prompt) - 1 + len(out)))


def _flips(mine, theirs, margins, prompt_len: int) -> dict:
    """Positions where some layer's set of chosen held experts differs, and
    the reference's margin at each (layer, position) that flipped."""
    import numpy as np

    differ = (np.asarray(mine) != np.asarray(theirs)).any(axis=-1)      # (layers, tokens)
    where = np.flatnonzero(differ.any(axis=0))
    at = np.sort(np.asarray(margins)[differ])
    return {"positions": int(differ.any(axis=0).sum()), "layer_positions": int(differ.sum()),
            "of_positions": int(differ.shape[1]), "by_layer": [int(x) for x in differ.sum(axis=1)],
            "in_the_answer": int((where >= prompt_len).sum()),
            "margin_at_flips": {"median": float(np.median(at)) if at.size else None,
                                "p99": float(np.quantile(at, 0.99)) if at.size else None,
                                "largest": [float(x) for x in at[-5:]]}}


class _Reference:
    """The plain reference's ``forward`` remembered by sequence, so that the
    HARNESS's comparison (``reference_check.main``, called below as it is)
    and this script's own look at the same rows pay for one pass."""

    def __init__(self, reference):
        self.real, self.seen = reference.forward, {}

    def __call__(self, params, tokens, **sizes):
        import numpy as np

        key = hash(tuple(int(t) for t in tokens))
        if key not in self.seen:
            x, held, margins = self.real(params, tokens, **sizes)
            self.seen[key] = (np.asarray(x), [np.asarray(m) for m in held],
                              [np.asarray(m) for m in margins])
        return self.seen[key]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--only", default=None, help="comma-separated controls")
    ap.add_argument("--probe-seeds", default="", help="further probe seeds for `configured`")
    ap.add_argument("--margins", default="", help="further routing margins to judge under")
    args = ap.parse_args()
    H.prepare_environment(args.rehearsal)
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import tempfile

    import jax
    import numpy as np

    from benchmark import reference_check
    from benchmark.reference import kimi_k2 as reference
    from ray_tpu.serve import llm as serve_llm

    config = H.load_config(H.manifest(), CONFIG)
    sizes = H.sizes(config, args.rehearsal)
    cfg = H.family_piece(config, "model_config")(sizes)
    ref_sizes = H.family_piece(config, "reference_sizes")(cfg)
    init = H.family_piece(config, "program_init")()
    seeded = serve_llm._seeded_params
    held = {"params": seeded(init, cfg, config["deployment"]["weights_seed"], 1)}
    tol = sizes["correctness"]["logit_tolerance"]
    margins = sorted({reference.ROUTING_MARGIN, *map(float, filter(None, args.margins.split(",")))})
    report = {"platform": jax.devices()[0].platform, "tolerance": tol,
              "routing_margin": reference.ROUTING_MARGIN, "controls": {}}
    remembered = _Reference(reference)

    def probes_of(seed):
        spec = dict(sizes["correctness"], probe_seed=seed)
        changed = dict(config, correctness=spec, rehearsal=dict(
            config["rehearsal"], correctness=dict(config["rehearsal"]["correctness"],
                                                  probe_seed=seed)))
        return serving.probe_prompts(changed, cfg.vocab_size, args.rehearsal)

    def harness_verdict(probes, outs, margin):
        """``reference_check.main`` itself, on the weights this process holds."""
        with tempfile.TemporaryDirectory() as tmp, \
                mock.patch.object(serve_llm, "_seeded_params", lambda *a: held["params"]), \
                mock.patch.object(reference, "forward", remembered), \
                mock.patch.object(reference, "ROUTING_MARGIN", margin):
            job, out = os.path.join(tmp, "job.json"), os.path.join(tmp, "verdict.json")
            with open(job, "w") as f:
                json.dump({"config": config, "probes": probes, "outs": outs,
                           "rehearsal": args.rehearsal}, f)
            reference_check.main([job, out])
            return H.load_json(out)

    def rows_of(forward, seq, at):
        """(whole logit rows at ``at``, choices (layers, tokens, held),
        margins (layers, tokens)) of the reference, nothing masked."""
        x, chose, margin = forward(held["params"], seq, **ref_sizes)
        with jax.default_matmul_precision("highest"):
            h = reference._rmsnorm(
                np.asarray(x)[np.asarray(at)],
                held["params"]["ln_f"]["scale"].astype("float32"), ref_sizes["eps"])
            logits = h @ held["params"]["lm_head"]["kernel"].astype("float32")
        return np.asarray(logits), np.stack(chose), np.stack(margin)

    def judge(name, probes, outs, rows, choices):
        remembered.seen.clear()
        line = {"prompt_lens": [len(p["prompt"]) for p in probes], "deficits": [],
                "row_margins": [], "flips": []}
        distance = []
        for (seq, at), out, got, mine, probe in zip(
                _sequences(probes, outs), outs, rows, choices, probes):
            want, theirs, margin = rows_of(remembered, seq, at)
            line["deficits"].append(
                [float(x) for x in want.max(axis=-1) - want[np.arange(len(out)), out]])
            line["row_margins"].append([float(x) for x in margin.min(axis=0)[np.asarray(at)]])
            if got is not None:
                distance.append(got - want)
            if mine is not None:
                line["flips"].append(_flips(mine, theirs, margin, len(probe["prompt"])))
        if distance:
            err = np.concatenate(distance)
            line["logit_rows"] = {"rms": float(np.sqrt((err**2).mean())),
                                  "max_abs": float(np.abs(err).max())}
        line["harness"] = {str(m): harness_verdict(probes, outs, m) for m in margins}
        verdict = line["harness"][str(reference.ROUTING_MARGIN)]
        report["controls"][name] = line
        H.emit("control", name=name, ok=verdict["ok"], max_deficit=verdict["max_deficit"],
               per_probe_max=verdict["per_probe_max"], unmasked_max=max(map(max, line["deficits"])),
               logit_rows=line.get("logit_rows"),
               flips=[(f["positions"], f["of_positions"], f["in_the_answer"],
                       f["margin_at_flips"]["largest"][-1:]) for f in line["flips"]])
        save()

    def save():
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(report, f)

    probes = probes_of(sizes["correctness"]["probe_seed"])
    controls = _controls(cfg)
    names = args.only.split(",") if args.only else list(controls) + [MANTISSA3]
    served = {}
    for name in names:
        if name != MANTISSA3:
            served[name] = (probes, *_serve_probes(
                controls[name], held["params"], sizes["engine"], probes, keep_logits=True))
            H.note(f"{name}: served")
    for seed in filter(None, args.probe_seeds.split(",")):
        other = probes_of(int(seed))
        served[f"configured@{seed}"] = (other, *_serve_probes(
            controls["configured"], held["params"], sizes["engine"], other, keep_logits=True))
    for name, run in served.items():
        judge(name, *run)

    if "configured" in served:  # tells something on a chip only
        # the reference's own products rounded to bfloat16, on the configured
        # run's sequences; its tokens are each row's own largest, so the rows
        # are judged here, by the harness's expression and the same margin
        line = {"deficits": [], "row_margins": [], "flips": []}
        distance = []
        for (seq, at), probe in zip(_sequences(probes, served["configured"][1]), probes):
            want, theirs, margin = rows_of(remembered, seq, at)
            with mock.patch.object(jax, "default_matmul_precision",
                                   lambda _, real=jax.default_matmul_precision: real("bfloat16")):
                got, mine, _ = rows_of(remembered.real, seq, at)
            out = got.argmax(axis=-1)
            line["deficits"].append(
                [float(x) for x in want.max(axis=-1) - want[np.arange(len(out)), out]])
            line["row_margins"].append([float(x) for x in margin.min(axis=0)[np.asarray(at)]])
            distance.append(got - want)
            line["flips"].append(_flips(mine, theirs, margin, len(probe["prompt"])))
        err = np.concatenate(distance)
        line["logit_rows"] = {"rms": float(np.sqrt((err**2).mean())),
                              "max_abs": float(np.abs(err).max())}
        kept = [d for ds, ms in zip(line["deficits"], line["row_margins"])
                for d, m in zip(ds, ms) if m >= reference.ROUTING_MARGIN]
        report["witness"] = line
        H.emit("witness", max_deficit=max(kept), rows=len(kept),
               unmasked_max=max(map(max, line["deficits"])), logit_rows=line["logit_rows"])
        save()

    if MANTISSA3 in names:
        # last: the weights are rounded where they lie (two trees do not
        # fit the chip), served, and made anew from the seed for the reference
        remembered.seen.clear()
        rounded = jax.jit(_to_mantissa3, donate_argnums=0)(held.pop("params"))
        run = _serve_probes(cfg, rounded, sizes["engine"], probes, keep_logits=True)
        del rounded
        gc.collect()
        held["params"] = seeded(init, cfg, config["deployment"]["weights_seed"], 1)
        judge(MANTISSA3, probes, *run)


if __name__ == "__main__":
    main()
