"""What the bring-up PR establishes about where the program runs:

- one compile-cache rule (``runtime.ensure_compile_cache``): the
  directory is placed from outside with ``JAX_COMPILATION_CACHE_DIR``,
  else it is ``<checkout>/.jax_cache``; importing the package touches
  nothing;
- ``chip_smoke.py`` finds no accelerator here, says so and exits
  non-zero — it does not fall back to the CPU;
- the smoke's explicit ``--tiny`` rehearsal runs the same control flow
  (four-device phase included, on the harness's virtual devices) to
  exit 0 and reports ``platform: "cpu"``;
- a Pallas-vs-XLA choice made under ``kernel="auto"`` can be read
  afterwards, with the compiler's message on a refusal;
- the bf16 peaks table resolves the v5e's ``device_kind``.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent


def _run(args, *, env_extra=None, env_drop=(), timeout=600):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], cwd=REPO_ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


# -- the cache rule ----------------------------------------------------------

def test_compile_cache_placed_from_outside_is_left_alone(tmp_path,
                                                         monkeypatch):
    from deeplearning4j_tpu import runtime

    prev = jax.config.jax_compilation_cache_dir
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert runtime.ensure_compile_cache() == placed
    # JAX read the variable itself at import; the repo sets nothing
    assert jax.config.jax_compilation_cache_dir == prev


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from deeplearning4j_tpu import runtime

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        want = str(REPO_ROOT / ".jax_cache")
        assert runtime.ensure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_importing_the_package_sets_no_cache_dir():
    p = _run(["-c", "import jax, deeplearning4j_tpu.runtime, "
                    "deeplearning4j_tpu.runtime.compile_cache; "
                    "print(jax.config.jax_compilation_cache_dir)"],
             env_drop=("JAX_COMPILATION_CACHE_DIR",))
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "None"


# -- no accelerator is an error, not a fallback ------------------------------

def test_chip_smoke_without_a_chip_fails_before_compiling():
    p = _run(["chip_smoke.py"])
    assert p.returncode != 0
    assert "no accelerator" in p.stderr and "platform=cpu" in p.stderr
    # the device line, and nothing after it: no phase ran, no result
    lines = p.stdout.strip().splitlines()
    assert lines and all(ln.startswith("[device]") for ln in lines), p.stdout
    assert '"ok"' not in p.stdout


def test_chip_smoke_tiny_rehearsal_passes_on_cpu(tmp_path):
    cache = str(tmp_path / "cache")
    p = _run(["chip_smoke.py", "--tiny"],
             env_extra={"JAX_COMPILATION_CACHE_DIR": cache})
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert f"compile_cache_dir={cache}" in p.stdout
    assert "[four_chip] phase=passed" in p.stdout      # 8 virtual devices
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == "cpu"         # never a chip claim


# -- a kernel choice can be read afterwards ----------------------------------

def test_auto_kernel_choice_keeps_its_reason(monkeypatch):
    from deeplearning4j_tpu.ops import kernel_select as ks

    here = ks.choose_kernel("auto", 256, "a fit", lambda blk: None)
    assert (here.name, here.block) == ("xla", 0)
    assert "cpu" in here.why
    forced = ks.choose_kernel("pallas", 256, "a fit", lambda blk: None)
    assert forced.name == "pallas-interpret" and "requested" in forced.why

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(ks.jax, "devices", lambda: [Chip()])
    taken = ks.choose_kernel("auto", 256, "a fit", lambda blk: None)
    assert (taken.name, taken.block, taken.interpret) == ("pallas", 256,
                                                          False)
    refused = ks.choose_kernel("auto", 256, "a fit",
                               lambda blk: "Mosaic says no")
    assert (refused.name, refused.block) == ("xla", 0)
    assert "Mosaic says no" in refused.why
    no_room = ks.choose_kernel("auto", 0, "a fit", lambda blk: None)
    assert no_room.name == "xla" and "VMEM" in no_room.why


# -- the peaks table ----------------------------------------------------------

def test_peak_flops_resolves_the_v5e_and_refuses_to_guess():
    from deeplearning4j_tpu.runtime.metrics import chip_peak_flops

    assert chip_peak_flops("TPU v5 lite") == 197e12    # as JAX reports it
    assert chip_peak_flops("TPU v5e") == 197e12
    assert chip_peak_flops("cpu") is None
    assert chip_peak_flops("TPU v99") is None
