"""chip_smoke.py's phases on the CPU at a tiny store.

The device check and the compiled-kernel check are steered here, in the
test (on the CPU JAX finds no TPU and the Pallas kernels run in interpret
mode); everything after them runs as on the chip.  Injected fallbacks and
ladder steps must make the smoke fail.
"""
import json

import pytest

import chip_smoke
from repro import compile_cache
from repro.compile_cache import _ROOT, cache_dir
from repro.core.gopt import GOpt
from repro.graphdb.engine import Engine
from repro.graphdb.jax_backend import FusedChain
from repro.graphdb.ldbc import generate_ldbc_streamed

SF = 0.2
REQUESTS = 16


def _steer_device(monkeypatch):
    import jax
    dev = jax.devices()[0]
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda chips: {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())})
    monkeypatch.setattr(chip_smoke, "require_compiled", lambda ops: None)


@pytest.fixture(scope="module")
def served():
    store = generate_ldbc_streamed(sf=SF, seed=7)
    gopt = GOpt(store, backend="jax")
    requests = chip_smoke.build_requests(store, 7, REQUESTS, wcoj=True)
    refs = [gopt.run(text, params, backend="numpy")[0]
            for _, text, params in requests]
    return gopt, requests, refs


def test_smoke_fails_without_tpu(capsys):
    assert chip_smoke.main(["--sf", str(SF)]) == 1
    cap = capsys.readouterr()
    assert "no TPU" in cap.err
    assert '"ok"' not in cap.out


def test_require_compiled_rejects_interpret(served):
    gopt, _, _ = served
    ops = gopt.spec.operators(gopt.store)
    assert ops._interpret                  # the CPU runs Pallas interpreted
    with pytest.raises(chip_smoke.SmokeFailure, match="interpret"):
        chip_smoke.require_compiled(ops)


def test_request_mix_anchors_non_hubs(served):
    gopt, requests, _ = served
    names = [name for name, _, _ in requests]
    assert set(names) == set(chip_smoke.IC_READS) | {
        q for q, *_ in chip_smoke.WCOJ_READS}
    n_person = gopt.store.v_count["PERSON"]
    for name, _, params in requests:
        if "pid" in params:
            assert n_person // 2 <= params["pid"] < n_person, name


def test_serve_and_check_passes(served):
    gopt, requests, refs = served
    out = chip_smoke.serve_and_check(gopt, "jax", requests, refs)
    assert len(out["walls"]) == 3
    assert out["kernels"].get("dispatch:fused_chain", 0) > 0
    assert out["kernels"].get("dispatch:wcoj", 0) > 0
    assert out["compiles"][-1] == 0        # the warm pass compiles nothing
    assert out["mid_plan_d2h"] == 0


def test_stacked_tail_error_fails_smoke(served, monkeypatch):
    gopt, requests, refs = served

    def boom(self, *a, **k):
        raise RuntimeError("injected segment-stack failure")

    monkeypatch.setattr(Engine, "_run_tails_stacked", boom)
    with pytest.raises(chip_smoke.SmokeFailure, match="stacked_tail_error"):
        chip_smoke.serve_and_check(gopt, "jax", requests, refs)


def test_ladder_step_fails_smoke(served, monkeypatch):
    """A fused chain that fails on the device steps its plan down the
    ladder (the request still ends ``done`` one rung lower): the smoke
    must refuse that."""
    gopt, requests, refs = served

    def boom(self, *a, **k):
        raise RuntimeError("injected fused-chain failure")

    monkeypatch.setattr(FusedChain, "run", boom)
    with pytest.raises(chip_smoke.SmokeFailure, match="ladder"):
        chip_smoke.serve_and_check(gopt, "jax", requests, refs)


def test_main_prints_device_json_last(served, monkeypatch, capsys):
    _steer_device(monkeypatch)
    # keep this process's compiles out of the checkout's cache directory
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "off")
    rc = chip_smoke.main(["--sf", str(SF), "--requests", str(REQUESTS)])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-3:]
    last = json.loads(out[-1])
    assert last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert any("cut: sf=" in line for line in out)


@pytest.mark.parametrize("env,expected", [
    ({"JAX_COMPILATION_CACHE_DIR": "/cache/here"}, "/cache/here"),
    ({}, str(_ROOT / ".jax_cache")),
    ({"JAX_COMPILATION_CACHE_DIR": ""}, str(_ROOT / ".jax_cache")),
])
def test_compile_cache_dir(env, expected):
    assert cache_dir(env) == expected


def test_compile_cache_dir_is_ignored_by_git():
    lines = (_ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in lines
