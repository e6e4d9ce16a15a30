"""Launch layer: mesh constructors, HLO collective parser, roofline math."""

import numpy as np
import pytest

from repro.launch import mesh as mesh_lib


def test_mesh_axes_helpers():
    # without touching device state: operate on names via fake mesh objects
    class FakeMesh:
        axis_names = ("data", "model")
    assert mesh_lib.batch_axes(FakeMesh()) == ("data",)
    assert mesh_lib.row_axes(FakeMesh()) == ("data", "model")

    class FakePod:
        axis_names = ("pod", "data", "model")
    assert mesh_lib.batch_axes(FakePod()) == ("pod", "data")


HLO_SAMPLE = """
  %ar = f32[16,128]{1,0} all-reduce(f32[16,128]{1,0} %x), replica_groups={}
  %ag.1 = bf16[32,256]{1,0} all-gather(bf16[2,256]{1,0} %y), dimensions={0}
  %rs = f32[4,64]{1,0} reduce-scatter(f32[64,64]{1,0} %z), dimensions={0}
  %cp = s32[8]{0} collective-permute(s32[8]{0} %w)
  %ars = f32[16,16]{1,0} all-reduce-start(f32[16,16]{1,0} %v)
  %nope = f32[2,2]{1,0} add(f32[2,2]{1,0} %a, f32[2,2]{1,0} %b)
"""


def test_collective_parser():
    # import parses XLA_FLAGS at module top; safe in-process since it only
    # sets an env var for future processes, not this one's backend
    from repro.launch import dryrun

    out = dryrun.parse_collectives(HLO_SAMPLE)
    assert out["all-reduce"]["count"] == 2
    assert out["all-reduce"]["result_bytes"] == 16 * 128 * 4 + 16 * 16 * 4
    assert out["all-gather"]["result_bytes"] == 32 * 256 * 2
    assert out["reduce-scatter"]["result_bytes"] == 4 * 64 * 4
    assert out["collective-permute"]["result_bytes"] == 8 * 4
    wire = dryrun.effective_wire_bytes(out, 16)
    assert wire > 0


def test_effective_wire_ring_model():
    from repro.launch import dryrun

    coll = {"all-reduce": {"count": 1, "result_bytes": 1000}}
    # ring all-reduce moves 2*(n-1)/n * bytes
    assert dryrun.effective_wire_bytes(coll, 16) == pytest.approx(
        2 * 1000 * 15 / 16)


def test_roofline_model_flops_sane():
    from benchmarks.roofline_report import model_flops

    # llama3 train_4k: 6 * 8e9 * 1.05e6 tokens ~ 5e16
    f = model_flops("llama3-8b", "train_4k")
    assert 3e16 < f < 8e16
    # decode: 2 * N * batch
    f = model_flops("llama3-8b", "decode_32k")
    assert 1e12 < f < 1e13
    # moe uses active params
    f_moe = model_flops("qwen3-moe-30b-a3b", "train_4k")
    f_if_dense = 6 * 30e9 * 256 * 4096
    assert f_moe < 0.3 * f_if_dense


# -- the serve entry point: exit status and compile-cache location ----------


def test_serve_main_exits_nonzero_on_request_errors(monkeypatch):
    """`main()` fails the process when a request ends in an error; an
    admission shed is reported but is not an error."""
    from types import SimpleNamespace

    from repro.launch import serve as launch

    def fake_serve(results):
        return lambda args: launch.ServeReport(
            plan=None, results=results, queries={}, summary={})

    ok = SimpleNamespace(ok=True, shed_reason=None)
    shed = SimpleNamespace(ok=False, shed_reason="deadline")
    failed = SimpleNamespace(ok=False, shed_reason=None)
    monkeypatch.setattr(launch, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(launch, "serve", fake_serve([ok, shed]))
    assert launch.main([]) == 0
    monkeypatch.setattr(launch, "serve", fake_serve([ok, failed]))
    assert launch.main([]) == 1


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env_dir", "repo_root"])
def test_compile_cache_location(tmp_path, from_env):
    """`enable_compile_cache` writes to $JAX_COMPILATION_CACHE_DIR when it
    is set, else to `.jax_cache/` at the repo root (here a stand-in root);
    run in a child process so this test process keeps no cache."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = Path(__file__).resolve().parents[1] / "src"
    code = (
        "import sys, pathlib, jax\n"
        "from repro.launch import serve\n"
        "serve.REPO_ROOT = pathlib.Path(sys.argv[1])\n"
        "print(serve.enable_compile_cache())\n"
        "jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(3))"
        ".block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(src), os.environ.get("PYTHONPATH", "")]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = tmp_path / ("env_cache" if from_env else ".jax_cache")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(want)
    assert any(want.iterdir())
    assert (tmp_path / ".jax_cache").exists() == (not from_env)
