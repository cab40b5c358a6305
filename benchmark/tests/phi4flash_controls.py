"""Controls for ``phi4-mini-flash-1chip``'s ``logit_tolerance``: what the
reference comparison reads on the configured programs, on programs held
one precision lower, on a program with the window mask dropped, and what
bf16 rounding ALONE reads in code that shares nothing with the program.

    python3 benchmark/tests/phi4flash_controls.py [--rehearsal] [--out FILE]

One process, on the chip at the published widths (``--rehearsal``: the
configuration's tiny sizes on a CPU, to try the script).  The served
programs (``HybridModelRunner``'s prefill chunk and decode, the engine's
``prefill_chunk``, block size and table) answer the configuration's probe
prompts greedily, one probe at a time in row 1 of a full decode batch; the
statistic is ``reference_check``'s: at each output position the reference's
logit of the program's token against the reference's largest.  A departure
is planted HERE, by overriding one method of the body or one field of the
model's configuration: the served programs hold no such switch.

* ``configured``: what the cell serves.  Also the program's whole logit
  rows against the reference's (rms, largest).
* ``bf16_scan_state``: the scan state held in bfloat16.  Must fail.
* ``bf16_stream``: the residual stream rounded to bfloat16 at every block's
  end.  Reads BELOW ``configured`` on the chip (0.057 against 0.084) and
  passes: one more rounding of the products' own size moves the largest of
  192 near-ties either way, which is why the limit cannot rest on it.
* ``no_window_mask``: a chunk's queries see every ring entry.  Must fail.
* ``q_rounded_once``: the window and full layers' queries keep float32
  until the pair form has scaled them by sqrt 2, so they are rounded to
  bfloat16 once and not twice (``ops.diff_attention._padded_queries``
  re-rounds a bfloat16 q).  Should read about what ``configured`` reads:
  the second rounding is one of some hundred a token, not a fault.
* ``mantissa3_weights``: every weight matrix rounded to float8_e4m3's 3 bits
  of mantissa (the reference keeps the weights as they are): the matrix products are where
  the configured path's distance comes from (``witness``), and this holds
  their one operand a precision lower.  Must fail, by a factor.
* ``witness`` (says something on a chip only): the plain reference ITSELF with its matrix
  products at the chip's default precision, which rounds both inputs to
  bfloat16 and accumulates in float32, against itself at ``highest``, on the
  configured run's sequences: the deficit of the witness's own greedy
  tokens, its logit rows' distance, and the relative distance of the
  residual stream after every layer.  If bf16 products alone, in code
  that has no cache, no ring and no kernel, read what the program reads,
  the program's distance is rounding and not a fault.
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

CONFIG = "phi4-mini-flash-1chip"
MANTISSA3 = "mantissa3_weights"


def _to_mantissa3(params):
    """Every matrix rounded to 3 bits of mantissa, float8_e4m3's, where
    bfloat16 has 7; the exponent stays.  By its bits: through
    ``astype(float8_e4m3fn)`` and back the weights reached a v5e's programs
    unrounded (the configured path's tokens and deficits, digit for digit)."""
    import jax
    import jax.numpy as jnp

    def rounded(w):
        if w.ndim < 2:
            return w
        uint, drop = {2: (jnp.uint16, 4), 4: (jnp.uint32, 20)}[w.dtype.itemsize]
        bits = jax.lax.bitcast_convert_type(w, uint) + uint(1 << (drop - 1))
        return jax.lax.bitcast_convert_type(bits >> drop << drop, w.dtype)

    return jax.tree.map(rounded, params)


def _controls(cfg):
    import jax.numpy as jnp

    from ray_tpu.models import phi4flash
    from ray_tpu.models.phi4flash import Phi4FlashBody
    from ray_tpu.ops import diff_attention

    class Bf16Stream(Phi4FlashBody):
        def _mlp(self, h, layer):
            return super()._mlp(h, layer).astype(jnp.bfloat16).astype(jnp.float32)

    class NoWindowMask(Phi4FlashBody):
        def _ring_seen(self, positions, held):
            return jnp.broadcast_to(held[None, :] >= 0, (positions.shape[0], held.shape[0]))

    class QRoundedOnce(Phi4FlashBody):
        def _qkv(self, x, layer):
            cfg = self.cfg
            _, k, v = super()._qkv(x, layer)
            nq = cfg.n_heads * cfg.head_dim
            y = phi4flash._layernorm(x, layer["ln1"], cfg.layer_norm_eps, self.dt)
            q = phi4flash._dot32(y, layer["qkv"]["kernel"][:, :nq]) + layer["qkv"][
                "bias"][:nq].astype(jnp.float32)
            return q.reshape(x.shape[0], cfg.n_heads, cfg.head_dim), k, v

    # a float32 q leaves the pair form scaled and float32: its ONE rounding
    # is here (a bfloat16 q comes out bfloat16 as before: nothing changes)
    pad, dt = diff_attention._padded_queries, jnp.dtype(cfg.dtype)
    diff_attention._padded_queries = lambda q, kv_heads: pad(q, kv_heads).astype(dt)

    def with_body(body):
        class Config(type(cfg)):
            def serving_body(self):
                return body(self)

        return Config(**dataclasses.asdict(cfg))

    return {
        "configured": cfg,
        "bf16_scan_state": dataclasses.replace(cfg, state_dtype="bfloat16"),
        "bf16_stream": with_body(Bf16Stream),
        "no_window_mask": with_body(NoWindowMask),
        "q_rounded_once": with_body(QRoundedOnce),
    }


def _serve_probes(cfg, params, engine: dict, probes: list, keep_logits: bool):
    """Each probe greedily through the served programs.  Returns (outs,
    logit rows a probe or None)."""
    import jax
    import numpy as np

    from ray_tpu.llm.cache import HybridConfig, HybridPool
    from ray_tpu.llm.model_runner import pack_knobs
    from ray_tpu.llm.state_runner import HybridModelRunner

    bs, chunk, slots = engine["block_size"], engine["prefill_chunk"], engine["max_slots"]
    runner = HybridModelRunner(cfg, params, bs)
    pool = HybridPool(
        HybridConfig(engine["num_blocks"], bs, engine["max_blocks_per_seq"], slots),
        runner.body.kv_layout(), runner.body.state_leaves(bs))
    greedy = pack_knobs(0, 0.0, 0, 1.0, 0)
    step = jax.jit(runner._decode_logits)
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
                *pool.arrays, buf, pos, len(piece), table, greedy)
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
            pool.arrays, batch = step(runner.params, pool.arrays, tokens, positions, tables)
            logits = batch[1]
        pool.free(name)
        outs.append(out)
        rows.append(np.stack(got) if keep_logits else None)
    del pool, runner, step
    gc.collect()
    return outs, rows


def _sequences(probes, outs):
    """As ``reference_check``: prompt and chosen tokens, the output rows."""
    for probe, out in zip(probes, outs):
        prompt = probe["prompt"]
        yield prompt + out[:-1], list(range(len(prompt) - 1, len(prompt) - 1 + len(out)))


def _deficits(logits, out):
    import numpy as np

    logits = np.asarray(logits)
    return logits.max(axis=-1) - logits[np.arange(len(out)), np.asarray(out)]


def _stream_distances(params, tokens, cfg):
    """The reference's layer loop twice in step, at ``highest`` and at the
    chip's default precision: the residual stream's relative distance
    after every layer."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import phi4flash as ref

    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    eps, sub, h, k_ = cfg.layer_norm_eps, cfg.subln_eps, cfg.n_heads, cfg.n_kv_heads
    n1 = cfg.mid // 2
    n2 = (cfg.n_layers - cfg.mid - 2) // 2
    x0 = params["embed"]["tokens"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)

    def layers(precision):
        with jax.default_matmul_precision(precision):
            x = x0
            for i in range(n1):
                x, _ = ref._ssm(x, at(params["seg1"]["ssm"], i), eps)
                yield x
                x, _, _ = ref._attention(x, at(params["seg1"]["window"], i), 2.0 * i + 1,
                                         cfg.sliding_window, h, k_, eps, sub)
                yield x
            x, memory = ref._ssm(x, params["memory"], eps)
            yield x
            x, k, v = ref._attention(x, params["full"], 2.0 * n1 + 1, 0, h, k_, eps, sub)
            yield x
            for i in range(n2):
                x = ref._gate_and_cross(x, at(params["seg2"], i), memory, k, v,
                                        2.0 * n1 + 2 + 2 * i, h, k_, eps, sub)
                yield x  # a memory gate and a cross layer

    # one precision after the other: a generator holds its precision only
    # while it alone runs
    return [float(jnp.linalg.norm(lo - hi) / jnp.linalg.norm(hi))
            for hi, lo in zip(list(layers("highest")), list(layers("bfloat16")))]


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
    params = _seeded_params(H.family_piece(config, "program_init")(), cfg,
                            config["deployment"]["weights_seed"], 1)
    probes = serving.probe_prompts(config, cfg.vocab_size, args.rehearsal)
    tol = sizes["correctness"]["logit_tolerance"]
    report = {"platform": jax.devices()[0].platform, "tolerance": tol,
              "prompt_lens": [len(p["prompt"]) for p in probes], "controls": {}}

    controls = _controls(cfg)
    names = args.only.split(",") if args.only else list(controls) + [MANTISSA3]

    def judge(name, outs, rows):
        per_probe, distance = [], []
        for (seq, at), out, got in zip(_sequences(probes, outs), outs, rows):
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
        if name != MANTISSA3:
            served[name] = _serve_probes(controls[name], params, sizes["engine"], probes,
                                         keep_logits=name == "configured")
            H.note(f"{name}: served")
    for name, (outs, rows) in served.items():
        judge(name, outs, rows)

    if "configured" in served:  # tells something on a chip only
        # the reference with bf16 products against itself, on the configured
        # run's sequences; ``logits_at`` asks for ``highest`` by name
        per_probe, distance = [], []
        for seq, at in _sequences(probes, served["configured"][0]):
            want = np.asarray(reference_logits(params, seq, at, cfg))
            with mock.patch.object(jax, "default_matmul_precision",
                                   lambda _, real=jax.default_matmul_precision: real("bfloat16")):
                got = np.asarray(reference_logits(params, seq, at, cfg))
            per_probe.append(float(_deficits(want, got.argmax(axis=-1)).max()))
            distance.append(got - want)
        err = np.concatenate(distance)
        seq, _ = list(_sequences(probes, served["configured"][0]))[1]
        line = {"max_deficit": max(per_probe), "per_probe_max": per_probe,
                "logit_rows": {"rms": float(np.sqrt((err**2).mean())),
                               "max_abs": float(np.abs(err).max())},
                "stream_distance_by_layer": _stream_distances(params, seq, cfg)}
        report["witness"] = line
        H.emit("witness", **line)

    if MANTISSA3 in names:
        # last: the weights are rounded where they lie (two trees do not
        # fit the chip), served, and made anew from the seed for the reference
        rounded = jax.jit(_to_mantissa3, donate_argnums=0)(params)
        del params
        outs, rows = _serve_probes(cfg, rounded, sizes["engine"], probes, keep_logits=False)
        del rounded
        gc.collect()
        params = _seeded_params(H.family_piece(config, "program_init")(), cfg,
                                config["deployment"]["weights_seed"], 1)
        judge(MANTISSA3, outs, rows)

    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
