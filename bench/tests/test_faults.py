"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole run of a cell at a small size (everything but
the look for a chip), with one of the faults a cell of its kind can have
planted in the program, and reads ``correct`` and the compared numbers
against the limits of the cell's own configuration file.  The sound run
of each kind comes out correct.  The serving tests run for every serving
configuration of ``BENCHMARK.json``."""

import pytest

import harness
from conftest import (SERVE_CONFIGS, SPEC, run_cell, serve_cell,
                      small_chat_mix, small_qr_config, small_serve_config)


def _serve(config):
    return run_cell(serve_cell(config), small_serve_config(config),
                    small_chat_mix())


def _qr():
    return run_cell("qr-2048.paper", small_qr_config(),
                    {"kind": "plans", "check_plans": 3}, seconds=1.0)


@pytest.mark.parametrize("config", SERVE_CONFIGS)
def test_sound_serving_run_is_correct(config):
    result, checks = _serve(config)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("config", SERVE_CONFIGS)
def test_served_token_altered_is_caught(monkeypatch, config):
    """A token altered where it is produced: greedy sampling returns the
    id after the argmax."""
    from repro.models import serving
    real = serving.sample_tokens

    def altered(logits, keys, temperature, top_k):
        tok, keys = real(logits, keys, temperature, top_k)
        return (tok + 1) % logits.shape[-1], keys

    monkeypatch.setattr(serving, "sample_tokens", altered)
    result, checks = _serve(config)
    assert not result["correct"]
    assert checks["served_gap_max"]["value"] > checks["served_gap_max"][
        "limit"]


@pytest.mark.parametrize("config", SERVE_CONFIGS)
def test_decode_step_returning_its_state_is_caught(monkeypatch, config):
    """A decode step that returns the cache it was given: the new
    token's keys and values are never written."""
    from repro.models import serving
    real = serving.decode_step

    def stale(params, cfg, cache, tokens, pos):
        logits, _ = real(params, cfg, cache, tokens, pos)
        return logits, cache

    monkeypatch.setattr(serving, "decode_step", stale)
    result, checks = _serve(config)
    assert not result["correct"]
    assert checks["served_gap_max"]["value"] > checks["served_gap_max"][
        "limit"]


def test_sound_qr_run_is_correct():
    result, checks = _qr()
    assert result["correct"], checks
    assert result["attempted"] > 0


def test_qr_megakernel_returning_its_state_is_caught(monkeypatch):
    """The engine's QR walk returns the tiles it was given."""
    import repro.engine as engine
    monkeypatch.setattr(engine, "qr_round_fn",
                        lambda *a, **k: lambda desc, pb, st, buffers:
                        buffers)
    result, checks = _qr()
    assert not result["correct"]
    assert checks["r_rel_err_max"]["value"] > checks["r_rel_err_max"][
        "limit"]


def test_qr_answer_altered_is_caught(monkeypatch):
    """One entry of R altered where the megakernel produces it."""
    import repro.engine as engine
    real = engine.qr_round_fn

    def altered(*a, **k):
        fn = real(*a, **k)

        def round_fn(desc, pb, statics, buffers):
            tiles, tmat = fn(desc, pb, statics, buffers)
            return tiles.at[0, 0, 1].add(1.0), tmat
        return round_fn

    monkeypatch.setattr(engine, "qr_round_fn", altered)
    result, checks = _qr()
    assert not result["correct"]
    assert checks["r_rel_err_max"]["value"] > checks["r_rel_err_max"][
        "limit"]


@pytest.mark.parametrize(
    "cell", [serve_cell(c) for c in SERVE_CONFIGS] + ["qr-2048.paper"])
def test_traced_run_reports_per_layer_metrics(cell):
    if cell == "qr-2048.paper":
        result, _ = run_cell(cell, small_qr_config(),
                             {"kind": "plans", "check_plans": 1},
                             seconds=1.0, trace=1)
        assert "sched_host_ms.plan" in result["metrics"]
    else:
        config = harness.find_cell(SPEC, cell)["config"]
        result, _ = run_cell(cell, small_serve_config(config),
                             small_chat_mix(), trace=1)
        assert "sched_host_ms.tick" in result["metrics"]
        assert "ttft_p50_ms" not in result["metrics"]
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
