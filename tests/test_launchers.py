"""Launcher-path smoke tests: CLI flags reach the head/index machinery
(notably ``--mips lsh``, exposed by launch/train.py and launch/serve.py)."""
import json
import sys
from pathlib import Path

import pytest

import repro.models.transformer as T


@pytest.fixture(autouse=True)
def _no_remat(monkeypatch):
    monkeypatch.setattr(T, "REMAT", False)


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    """main() turns JAX's persistent compilation cache on for the process;
    in-process launcher tests keep it off for the rest of the worker."""
    from repro.launch import serve, train

    for mod in (serve, train):
        monkeypatch.setattr(mod, "enable_compile_cache", lambda: "")


def test_compile_cache_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; else a fixed path at the
    checkout root."""
    import jax

    from repro.launch import compile_cache as cc

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert cc.enable_compile_cache() == "/elsewhere"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = cc.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert Path(path) == Path(cc.__file__).resolve().parents[3] / ".jax_cache"


def _json_tail(out: str) -> dict:
    return json.loads(out[out.index("{"):])


def test_train_launcher_mips_lsh(tmp_path, monkeypatch, capsys):
    from repro.launch import train as train_cli

    monkeypatch.setattr(sys, "argv", [
        "train", "--arch", "tinyllama-1.1b", "--smoke", "--steps", "2",
        "--batch", "2", "--seq", "16", "--head", "amortized",
        "--mips", "lsh", "--vocab", "4096", "--index-refresh-every", "2",
        "--workdir", str(tmp_path),
    ])
    train_cli.main()
    result = _json_tail(capsys.readouterr().out)
    assert result["status"] == "done"
    # the LSH index was built AND refreshed through the launcher path
    assert result["index_refreshes"] == 1


def test_serve_launcher_mips_lsh(monkeypatch, capsys):
    from repro.launch import serve as serve_cli

    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "tinyllama-1.1b", "--smoke", "--requests", "2",
        "--slots", "2", "--new-tokens", "2", "--max-seq", "32",
        "--head", "amortized", "--mips", "lsh", "--vocab", "4096",
    ])
    serve_cli.main()
    result = _json_tail(capsys.readouterr().out)
    assert result["requests"] == 2
    assert result["decoded_tokens"] == 4
    assert result["index_mb"] > 0  # an actual LSH index served the probe


def test_train_launcher_mips_ivfpq(tmp_path, monkeypatch, capsys):
    """--mips ivfpq reaches the quantized index end to end: build through
    the launcher, codebooks refreshed with the embeddings on schedule."""
    from repro.launch import train as train_cli

    monkeypatch.setattr(sys, "argv", [
        "train", "--arch", "tinyllama-1.1b", "--smoke", "--steps", "2",
        "--batch", "2", "--seq", "16", "--head", "amortized",
        "--mips", "ivfpq", "--vocab", "4096", "--index-refresh-every", "2",
        "--workdir", str(tmp_path),
    ])
    train_cli.main()
    result = _json_tail(capsys.readouterr().out)
    assert result["status"] == "done"
    assert result["index_refreshes"] == 1


def test_serve_launcher_mips_ivfpq(monkeypatch, capsys):
    from repro.launch import serve as serve_cli

    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "tinyllama-1.1b", "--smoke", "--requests", "2",
        "--slots", "2", "--new-tokens", "2", "--max-seq", "32",
        "--head", "amortized", "--mips", "ivfpq", "--vocab", "4096",
    ])
    serve_cli.main()
    result = _json_tail(capsys.readouterr().out)
    assert result["requests"] == 2
    assert result["decoded_tokens"] == 4
    assert result["index_mb"] > 0  # a quantized index served the probe


def test_launchers_reject_unknown_mips(monkeypatch, capsys):
    from repro.launch import train as train_cli

    monkeypatch.setattr(sys, "argv", [
        "train", "--arch", "tinyllama-1.1b", "--smoke", "--mips", "faiss",
    ])
    with pytest.raises(SystemExit):
        train_cli.main()
