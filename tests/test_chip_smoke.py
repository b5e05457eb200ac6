"""Tier-1 rehearsal of ``chip_smoke.py`` at toy size on the CPU.

The real run needs the chip (``chiprun -- python chip_smoke.py``);
here the same phases run on the CPU backend through ``main()``'s
test-only ``allow_platform`` argument, so a broken path, argument or
assertion is found without chip time. Also pinned: the script refuses
to run without a TPU, its device proof fails when the breaker hides a
device failure behind the host fallback, the exact shape of its last
line, and where the compile cache is placed.
"""

import json
import os

import jax
import pytest

import chip_smoke

# smallest sizes that still cross every threshold: device regime
# (>= 1024 filters), retained index on the device (>= 4096 names),
# bitmap fan-out (> 1024 subscribers)
_TOY = ["--filters", "1500", "--retained", "4200", "--messages", "240",
        "--fan", "1030"]


def _lines(capsys):
    return [ln for ln in capsys.readouterr().out.splitlines() if ln]


def test_rehearsal_phases_and_last_line(capsys):
    rc = chip_smoke.main(_TOY, allow_platform="cpu")
    lines = _lines(capsys)
    assert rc == 0, "\n".join(lines[-15:])
    last = json.loads(lines[-1])
    d0 = jax.devices()[0]
    assert last == {"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}
    text = "\n".join(lines[:-1])
    assert lines[0].startswith("device: platform=cpu")
    assert "reduced: filters=1500 retained=4200 messages=240" in text
    assert "native: built libemqx_native.so" in text
    assert "1500 filters subscribed" in text
    assert "4200 retained names stored" in text
    # every delivered set compared, every special path driven
    for needle in ("client c_plus", "client c_hash", "client c_sys",
                   "client c_deep", "client c_hot", "$share group g1",
                   "1M-filter sink", "bitmap fan-out",
                   "retained: late subscribe"):
        row = next(ln for ln in lines if needle in ln)
        assert row.endswith("equal"), row
    assert "MISMATCH" not in text
    # the device proof, as printed
    proof = [ln for ln in lines if ln.startswith("proof:")]
    assert '"breaker.failures": 0' in proof[0]
    assert '"retain_index.strikes": 0' in proof[0]
    assert "breaker=closed" in proof[0]
    assert "paths {'device':" in proof[1] and "0 with bucket 0" in proof[1]
    assert "walk mode=" in proof[2]
    assert any(ln.startswith("round 1:") and "warm" in ln
               for ln in lines)
    assert any(ln.startswith("compile:") for ln in lines)


def test_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as ei:
        chip_smoke.main([])
    assert ei.value.code not in (0, None)
    lines = _lines(capsys)
    # the device line only: no phase ran, no result was printed
    assert len(lines) == 1 and lines[0].startswith("device: ")


def test_device_proof_fails_when_the_host_covers_for_the_device(capsys):
    """One injected walk failure: the breaker serves that batch from
    the host oracle, every delivery is still right — and the smoke
    must fail, because the device did not do the work."""
    from emqx_tpu import faults

    def sabotage(node):
        faults.set_master(True)
        faults.arm("device.walk", times=1)

    try:
        rc = chip_smoke.main(_TOY, allow_platform="cpu",
                             sabotage=sabotage)
    finally:
        faults.clear()
        faults.set_master(False)
    lines = _lines(capsys)
    assert rc == 1
    assert lines[-1].startswith("FAILED: device path failed over")
    assert not any(ln.startswith('{"ok"') for ln in lines)
    assert "MISMATCH" not in "\n".join(lines)  # deliveries were right
    proof = next(ln for ln in lines if ln.startswith("proof: counters"))
    assert '"breaker.failures": 1' in proof


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path,
                                 placed_from_outside):
    """JAX_COMPILATION_CACHE_DIR set => no directory set in code;
    unset => <repo>/.jax_cache, whatever the working directory."""
    from emqx_tpu import profiling

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.chdir(tmp_path)
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if placed_from_outside:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / "outside"))
            profiling.enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir is None
            assert profiling.compile_cache_dir() == \
                str(tmp_path / "outside")
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR",
                               raising=False)
            profiling.enable_compile_cache()
            want = os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == want
            assert profiling.compile_cache_dir() == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_entry_refuses_to_run_without_a_tpu():
    """No fallback on the measurement paths: on a host without a chip
    ``entry()`` raises (no CPU number under a device metric's name)."""
    import __graft_entry__ as ge
    with pytest.raises(RuntimeError, match="no TPU"):
        ge.entry()
