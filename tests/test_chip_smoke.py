"""``chip_smoke.py`` on the CPU: its phases and oracle checks at ``--small``.

The script drives the served query paths on a TPU; here the same phases run
at a tiny size on the XLA backend, in this process, so a change that breaks
a phase or an oracle check fails the CPU suite before a chip run.
"""

import importlib.util
import json
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _last_line(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    return lines, lines[-1] if lines else ""


def test_small_run_passes_every_phase(smoke, capsys):
    assert smoke.main(["--small"]) == 0
    lines, last = _last_line(capsys)
    dev = jax.devices()[0]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}
    text = "\n".join(lines)
    assert "search smoke timings" in text and "store smoke timings" in text
    assert "dispatch_failures=0 fallbacks=0" in text


def test_full_run_refuses_without_a_tpu(smoke, capsys):
    if jax.default_backend() == "tpu":
        pytest.skip("checks the refusal without an accelerator")
    assert smoke.main([]) != 0
    lines, last = _last_line(capsys)
    assert '"ok"' not in last and "no TPU found" in last
