"""What the chip bring-up (PR 21) added, as far as a CPU can check it.

The chip itself is checked by ``chip_smoke.py`` through the chip tool;
these tests pin the rules that keep a CPU or interpreted run from passing
for a chip run: where the compile cache goes, that a driver never opens a
backend, that detection never asks jax, that ``auto`` follows one rule,
and that the smoke refuses a machine without a TPU.
"""

import os
import shutil
import subprocess
import sys
import tempfile

import numpy as np
import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV = dict(os.environ, PYTHONPATH=_REPO_ROOT, JAX_PLATFORMS="cpu")


# ------------------------------------------------------------ compile cache


def test_compile_cache_env_var_set_means_code_sets_nothing(monkeypatch):
    from ray_tpu._private import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/outside")
    assert compile_cache.compile_cache_dir("tpu") is None
    assert compile_cache.compile_cache_dir("cpu") is None


def test_compile_cache_unset_is_one_fixed_path_inside_the_checkout(monkeypatch):
    from ray_tpu._private import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.compile_cache_dir("tpu")
    assert path == os.path.join(_REPO_ROOT, ".jax_cache")
    assert path == compile_cache.compile_cache_dir("tpu")  # no pid/time in it
    tmp = os.path.realpath(tempfile.gettempdir())
    assert not os.path.realpath(path).startswith(tmp + os.sep)
    # CPU test runs must not fill a directory the chip tool copies
    assert compile_cache.compile_cache_dir("cpu") is None


def test_ensure_compile_cache_on_cpu_leaves_jax_config_alone(monkeypatch):
    import jax

    from ray_tpu._private import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    compile_cache.ensure_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before
    stats = compile_cache.stats()
    assert stats["dir"] == before and stats["hits"] <= stats["requests"]


# --------------------------------------------------- one process per chip


def test_init_after_import_jax_opens_no_backend():
    """``ray_tpu.init()`` in a driver that has jax imported (importing
    ``ray_tpu.serve.llm`` imports it) must leave the backend unopened:
    the chip belongs to the replica worker."""
    code = (
        "import jax\n"
        "import ray_tpu, ray_tpu.serve.llm\n"
        "import jax._src.xla_bridge as xb\n"
        "ray_tpu.init(num_cpus=1)\n"
        "assert not xb._backends, list(xb._backends)\n"
        "ray_tpu.shutdown()\n"
        "print('NO_BACKEND')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=_ENV, capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "NO_BACKEND" in out.stdout


def test_detect_num_chips_reads_device_nodes_not_jax(monkeypatch):
    from ray_tpu.accelerators import tpu

    for var in ("RAY_TPU_CHIPS", "TPU_CHIPS_PER_HOST", "TPU_VISIBLE_CHIPS",
                "TPU_ACCELERATOR_TYPE", "RAY_TPU_ACCELERATOR_TYPE"):
        monkeypatch.delenv(var, raising=False)
    # no TPU marker in the environment: /dev/vfio groups (any passed-through
    # PCI device has one) are not chips, /dev/accel<N> nodes are
    nodes = {"accel": 0, "vfio": 2}
    monkeypatch.setattr(
        tpu.glob, "glob",
        lambda pat: ["x"] * nodes["accel" if "accel" in pat else "vfio"],
    )
    assert tpu.detect_num_chips() == 0
    nodes["accel"] = 1
    assert tpu.detect_num_chips() == 1
    # a host handed ONE chip of a four-chip slice type has one chip
    nodes.update(accel=0, vfio=1)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    assert tpu.detect_num_chips() == 1
    nodes["vfio"] = 0
    assert tpu.detect_num_chips() == 4  # no nodes visible: the declared type
    assert "jax" not in tpu.__dict__


# ------------------------------------------------------------ one rule each


def test_auto_never_picks_a_pallas_kernel_off_tpu():
    from ray_tpu.ops import attention, paged_attention

    assert paged_attention.auto_impl(16, 256) == "xla"
    assert attention.auto_impl(1024) == "xla"


def test_auto_rules_on_a_tpu_backend(monkeypatch):
    import jax

    from ray_tpu.ops import attention, paged_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_attention.auto_impl(16, 256) == "pallas"
    assert paged_attention.auto_impl(8, 128) == "pallas"
    assert paged_attention.auto_impl(4, 256) == "xla"    # sublanes
    assert paged_attention.auto_impl(16, 64) == "xla"    # lanes
    assert attention.auto_impl(1024) == "flash"
    assert attention.auto_impl(64) == "xla"
    assert attention.auto_impl(1000) == "xla"


def test_flash_on_tpu_refuses_blocks_it_cannot_tile(monkeypatch):
    """Never a silent change of implementation: the old fallback to
    ``_xla_attention`` inside ``flash_attention`` is an error now."""
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    q = jnp.zeros((1, 2, 64, 16), jnp.float32)
    with pytest.raises(ValueError, match="tile by 128"):
        fa.flash_attention(q, q, q)


# ------------------------------------------------------- weights and report

TINY = dict(vocab_size=128, seq_len=64, d_model=64, n_layers=2, n_heads=4,
            rotary_dim=8, remat=False, attn_impl="xla", fused_loss=False)


def test_seeded_params_match_eager_init_and_take_the_compute_dtype():
    """Same seed, same weights as the eager init — to the last place or
    one off it: XLA may round a fused ``normal * scale`` differently from
    the two eager ops."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gptj import GPTJConfig, gptj_init
    from ray_tpu.serve.llm import _seeded_params

    cfg32 = GPTJConfig(dtype="float32", **TINY)
    want = gptj_init(jax.random.PRNGKey(3), cfg32)
    got = _seeded_params(gptj_init, cfg32, 3, 1)
    for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-7)
    bf16 = _seeded_params(gptj_init, GPTJConfig(dtype="bfloat16", **TINY), 3, 1)
    assert {x.dtype for x in jax.tree_util.tree_leaves(bf16)} == {jnp.dtype("bfloat16")}


@pytest.mark.skipif(
    "len(__import__('jax').devices()) < 2", reason="needs >=2 devices"
)
def test_seeded_params_under_tp_are_born_sharded():
    import jax

    from ray_tpu.models.gptj import GPTJConfig, gptj_init
    from ray_tpu.serve.llm import _seeded_params

    cfg = GPTJConfig(dtype="float32", **TINY)
    params = _seeded_params(gptj_init, cfg, 0, 2)
    q = params["blocks"]["q"]["kernel"]
    assert len(q.sharding.device_set) == 2
    assert q.addressable_shards[0].data.shape[-1] == q.shape[-1] // 2
    # same values as the single-device init: sharding changes placement only
    want = gptj_init(jax.random.PRNGKey(0), cfg)["blocks"]["q"]["kernel"]
    np.testing.assert_allclose(np.asarray(q), np.asarray(want), rtol=3e-7)


def test_mosaic_kernels_reads_kernel_names_from_lowered_text():
    from ray_tpu.util.device_prof import mosaic_kernels

    class Lowered:
        def as_text(self):
            return (
                '%3:2 = stablehlo.custom_call @tpu_custom_call(%0, %1) '
                '{backend_config = "{...}", kernel_name = "_fwd_kernel", x = 1}\n'
                '%4 = stablehlo.custom_call @Sharding(%3) {kernel_name = "no"}\n'
                '%5 = stablehlo.custom_call @tpu_custom_call(%4) '
                '{backend_config = "{...}", kernel_name = "_bwd_kernel"}\n'
            )

    assert mosaic_kernels(Lowered()) == ["_fwd_kernel", "_bwd_kernel"]


def test_engine_device_report_names_device_and_step_contents():
    import jax

    from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
    from ray_tpu.models.gptj import GPTJConfig, gptj_init

    cfg = GPTJConfig(dtype="float32", **TINY)
    eng = LLMEngine(
        cfg, gptj_init(jax.random.PRNGKey(0), cfg),
        EngineConfig(max_slots=2, num_blocks=16, block_size=4,
                     max_blocks_per_seq=8, prefill_chunk=8, spec_k=2),
    )
    eng.warmup()
    rep = eng.device_report()
    assert rep["platform"] == "cpu" and rep["device_count"] == len(jax.devices())
    assert set(rep["versions"]) == {"jax", "jaxlib", "libtpu"}
    att = rep["attention"]
    assert att["auto_rule"] == "xla"
    # on a CPU no step may claim a compiled kernel
    assert att["mosaic_kernels"] == {
        "decode": [], "prefill": [], "fork": [], "verify": []
    }
    assert set(rep["first_call_s"]) == {"decode", "prefill", "fork", "verify"}
    # lowering the steps for the report is not a retrace: the next calls
    # of every site find the jit caches as the warm-up left them
    eng.generate([5, 9, 7, 5, 9, 7, 5, 9], SamplingParams(max_tokens=6))
    assert eng.stats()["retraces"] == 0
    assert {k: s["cache_size"] for k, s in eng.runner.prof.stats().items()} == {
        k: s["cache_size"] for k, s in rep["jit_sites"].items()
    }


# -------------------------------------------------------------- the smoke


def test_chip_smoke_fails_without_a_tpu():
    """Alone in a directory, on a machine with no TPU: non-zero exit, no
    result line — and quickly, at the probe."""
    with tempfile.TemporaryDirectory() as d:
        shutil.copy(os.path.join(_REPO_ROOT, "chip_smoke.py"), d)
        env = {k: v for k, v in _ENV.items() if k != "PYTHONPATH"}
        out = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=d, env=env,
            capture_output=True, text=True, timeout=120,
        )
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout
