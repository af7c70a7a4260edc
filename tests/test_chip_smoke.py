"""``chip_smoke.py`` on the CPU: it must refuse to report without a TPU,
and its one-chip phases must run and pass their checks at a tiny size."""
import importlib.util
import json
from pathlib import Path

import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, monkeypatch, tmp_path, capsys):
    # a named cache directory: use_compile_cache then sets nothing here
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs 1 TPU" in out.err


def test_one_chip_phases_pass_at_tiny_size(smoke):
    meter = smoke.CompileMeter()
    chan = smoke.plan_and_channel(meter, n_nodes=16, rounds=4)
    assert chan["anchor"]["w_eff_equal"]
    assert chan["anchor"]["t_comm_max_rel_err"] < 1e-9
    assert smoke.mix_is_exact()["identity_mix_max_err"] == 0.0
    tr = smoke.train(meter, "compressed_int8", seeds=2, epochs=1)
    assert tr["payload"] == "int8" and tr["rounds"] == 8
    assert tr["last_acc_min"] > 1.0 / smoke.N_CLASSES
    json.dumps([chan, tr])                  # phase lines stay printable


def test_failed_check_raises(smoke):
    with pytest.raises(smoke.SmokeFailure, match="wrong"):
        smoke.require(False, "wrong")
