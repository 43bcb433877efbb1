"""The compression CLI (``repro.launch.compress.main``): the one entry point
that builds a ``CompressOptions`` from outside input."""
import pytest

from repro.launch import compress as cli

QUICK = ["--dataset", "e3sm", "--quick", "--epochs-scale", "0.1"]


@pytest.fixture(autouse=True)
def _no_cache_placement(monkeypatch, tmp_path):
    # with the variable set, ``main`` leaves the process-wide compile cache
    # where it is instead of pointing it into the repo for later tests
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


@pytest.mark.parametrize("argv", [
    ["--verify"],                               # --verify needs --out
    ["--retries", "2"],                         # fault tolerance needs --stream
    ["--chunk-hyperblocks", "0"],               # CompressOptions' ConfigError
], ids=["verify-without-out", "retries-without-stream", "zero-chunk"])
def test_cli_rejects_bad_arguments(argv):
    with pytest.raises(SystemExit) as e:
        cli.main(QUICK + argv)
    assert e.value.code != 0


def test_cli_batch_run_writes_and_verifies(tmp_path, capsys):
    out = tmp_path / "a.rba"
    assert cli.main(QUICK + ["--out", str(out), "--verify"]) == 0
    assert out.stat().st_size > 0
    assert "verify OK" in capsys.readouterr().out


def test_cli_stream_writes_the_batch_bytes(tmp_path):
    paths = {mode: tmp_path / f"{mode}.rba" for mode in ("batch", "stream")}
    common = QUICK + ["--seed", "3", "--chunk-hyperblocks", "8"]
    assert cli.main(common + ["--out", str(paths["batch"])]) == 0
    assert cli.main(common + ["--out", str(paths["stream"]), "--stream"]) == 0
    assert paths["stream"].read_bytes() == paths["batch"].read_bytes()
