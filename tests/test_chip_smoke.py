"""chip_smoke.py on the CPU: its phases at the reduced preset (the Pallas
paged kernels run interpreted), the teacher-forced check and its failure on
a corrupted token, the refusal to run without a TPU, and the helpers it
leans on (compile-cache directory, replica placement, the serve driver's
exit status)."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import reduced
from repro.launch import compile_cache, serve
from repro.models import api, reference
from repro.models import transformer as tfm

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(_spec)
sys.modules["chip_smoke"] = chip_smoke       # dataclasses look it up
_spec.loader.exec_module(chip_smoke)

SMALL = chip_smoke.Sizes(requests=4, min_prompt=8, max_prompt=40, max_new=6,
                         slots=4, max_len=64, block_size=8, reduced=True)


@pytest.fixture(scope="module")
def served():
    clock = chip_smoke.CompileClock()
    res = chip_smoke.phase_dense(SMALL, 0, clock)
    paged = chip_smoke.phase_paged(res["cfg"], res["params"], res["prompts"],
                                   SMALL, clock)
    return res, paged


def test_phases_serve_and_pass_the_teacher_forced_check(served):
    res, paged = served
    assert all(len(o) == SMALL.max_new + 1 for o in res["outputs"] + paged)
    for outs in (res["outputs"], paged):
        worst = chip_smoke.check_outputs("t", res["params"], res["cfg"],
                                         res["prompts"], outs)
        assert 0.0 <= worst <= chip_smoke.MARGIN_TOL
    # float32 preset: the paged kernels and the dense path agree exactly
    assert chip_smoke.token_agreement(res["outputs"], paged) == 1.0


def test_teacher_forced_check_fails_on_a_corrupted_token(served):
    res, _ = served
    params, cfg, prompts = res["params"], res["cfg"], res["prompts"]
    outs = [list(o) for o in res["outputs"]]
    # replace served token 3 of request 0 with the reference's least likely
    seq = np.concatenate([prompts[0], outs[0][:3]])[None]
    at = np.asarray([[len(prompts[0]) + 2]])
    logits = np.asarray(reference.reference_logits(params, cfg, seq, at))
    outs[0][3] = int(logits[0, 0].argmin())
    with pytest.raises(SystemExit, match="teacher-forced check failed"):
        chip_smoke.check_outputs("t", params, cfg, prompts, outs)


def test_reference_matches_the_model_forward():
    cfg = reduced(get_config("internlm2-1.8b"))
    params = api.init(jax.random.PRNGKey(3), cfg)[0]
    toks = np.random.RandomState(3).randint(0, cfg.vocab, (2, 24))
    at = np.tile(np.arange(24), (2, 1))
    ref = reference.reference_logits(params, cfg, toks, at)
    got, _ = tfm.forward(params, cfg, tokens=jnp.asarray(toks, jnp.int32))
    np.testing.assert_allclose(np.asarray(got)[..., :cfg.vocab], ref,
                               atol=1e-4, rtol=1e-4)


def test_reference_refuses_layers_it_does_not_implement():
    with pytest.raises(NotImplementedError, match="layer kinds"):
        reference.check_supported(reduced(get_config("falcon-mamba-7b")))


def test_main_exits_nonzero_without_a_tpu():
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert "needs a TPU" in str(e.value.code)
    assert "'cpu'" in str(e.value.code)


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_repo", "alone_in_a_directory"])
def test_script_fails_without_a_tpu(tmp_path, alone):
    """Run as the chip check runs it: no result line, a non-zero exit —
    both in the repo and from a directory holding only the script."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = str(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, script], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    if not alone:
        assert "platform 'cpu'" in r.stderr


@pytest.fixture
def restore_cache_config(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    yield monkeypatch
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_compile_cache_keeps_a_directory_the_environment_sets(
        restore_cache_config, tmp_path):
    restore_cache_config.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_repo(restore_cache_config):
    restore_cache_config.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.setup_compile_cache() == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert jax.config.jax_compilation_cache_dir == want


class _FakeChip:
    platform = "tpu"

    def __init__(self, i):
        self.id = i


def test_replica_devices(monkeypatch):
    assert serve.replica_devices(3) == [None] * 3      # CPU: shared
    chips = [_FakeChip(i) for i in range(4)]
    monkeypatch.setattr(jax, "devices", lambda: chips)
    assert serve.replica_devices(4) == chips
    with pytest.raises(ValueError, match="5 exceeds the 4 tpu devices"):
        serve.replica_devices(5)


def test_serve_exits_nonzero_when_a_request_does_not_complete():
    assert serve._incomplete(["max_new", "max_len"], [[1], [2]]) == []
    bad = serve._incomplete(["max_new", "rejected_prompt_too_long", "error"],
                            [[1], [], TimeoutError("late")])
    assert len(bad) == 3
    # a prompt longer than the whole paged pool is rejected by the engine
    with pytest.raises(SystemExit, match="1 of 1 requests did not complete"):
        serve.main(["--reduced", "--paged", "--block-size", "8",
                    "--kv-blocks", "2", "--max-len", "64", "--requests", "1",
                    "--min-prompt", "40", "--max-prompt", "40",
                    "--max-new", "2", "--slots", "1"])


def test_pipeline_phase_on_four_host_devices():
    """The four-chip pipeline comparison on four virtual CPU devices (a
    subprocess: the device count must be set before JAX starts)."""
    code = ("import os, sys\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=4'\n"
            f"sys.path.insert(0, {ROOT!r})\n"
            "import chip_smoke\n"
            "chip_smoke.phase_pipeline(0, rows_per_shard=64)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "4-device data mesh" in r.stdout and "n_dropped=0" in r.stdout
