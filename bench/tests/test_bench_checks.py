"""The correctness check of the cell: a sound run is correct, and a run whose
timed path is broken underneath, or a lower-precision control in the
program's place, is not. Runs on the CPU at sizes a test run can hold."""
import pytest

from bench.tests._cells import run


def test_endpoint_sound_run_is_correct():
    out = run(controls=("bfloat16", "int8", "float8_e4m3fn"))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    lim, ctl = out["checks"], out["control_checks"]
    # lower-precision weights in the program's place differ from the
    # image's bytes, and move the logits far from the program's
    for k in ("bfloat16", "int8", "float8_e4m3fn"):
        assert ctl[k]["params_bytes_wrong"] > 0
        assert ctl[k]["logit_rel_err"] > 100 * lim["logit_rel_err"]["value"]
    # at this size fp8 weights already fail the logit limit
    assert ctl["float8_e4m3fn"]["logit_rel_err"] > lim["logit_rel_err"]["limit"]


def test_endpoint_altered_token_fails(monkeypatch):
    from repro.serving.engine import ServingEngine
    sample = ServingEngine._sample
    monkeypatch.setattr(ServingEngine, "_sample",
                        lambda self, z: (sample(self, z) + 1) % z.shape[-1])
    out = run()
    assert not out["correct"]
    assert out["checks"]["tokens_not_greedy"]["value"] > 0


def test_endpoint_step_that_keeps_its_state_fails(monkeypatch):
    import repro.serving.engine as engine_mod
    make = engine_mod.make_serve_step_with_logits

    def stale(cfg):
        step = make(cfg)

        def serve_step(params, state, token):
            logits, _ = step(params, state, token)
            return logits, state
        return serve_step
    monkeypatch.setattr(engine_mod, "make_serve_step_with_logits", stale)
    out = run()
    assert not out["correct"]
    c = out["checks"]["logit_rel_err"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("after", [0, 1])
def test_endpoint_corrupted_migration_fails(after, monkeypatch):
    """A page flipped in every restore, or only in restores after the first
    (what a cache kept across restores could get wrong)."""
    from repro.core.migration import PageServer
    fetch = PageServer.fetch_pages
    seen = []

    def flip(self, first, n):
        pages = fetch(self, first, n)
        if first == 0:
            seen.append(1)
        if len(seen) > after:
            pages[0, 0] ^= 0x40
        return pages
    monkeypatch.setattr(PageServer, "fetch_pages", flip)
    out = run()
    assert not out["correct"]
    assert out["checks"]["params_bytes_wrong"]["value"] > 0
