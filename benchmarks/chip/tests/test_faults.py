"""The comparison that decides ``correct`` sees the faults a serving cell
can have: with the timed path broken underneath, a whole toy run reports
``correct`` false."""
import jax
import pytest

from test_rehearsal import run_toy, toy_bench  # noqa: F401


def _break_decode(monkeypatch, fault):
    from repro.serving import backend

    original = backend.continuous_step_programs

    def programs(cfg, geometry):
        prefill, graft, decode = original(cfg, geometry)
        half = geometry.n_slots // 2

        def broken(params, pool, tables, token, pos):
            tok, new_pool = decode(params, pool, tables, token, pos)
            if fault == "token_altered":
                return (tok + 1) % cfg.vocab_size, new_pool
            if fault == "state_unchanged":
                return tok, pool
            # half of the batch left out: its rows keep their last token
            return tok.at[half:].set(token[half:]), new_pool

        return prefill, graft, jax.jit(broken)

    monkeypatch.setattr(backend, "continuous_step_programs", programs)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged", "half_batch"])
def test_broken_decode_is_not_correct(toy_bench, monkeypatch, fault):  # noqa: F811
    _break_decode(monkeypatch, fault)
    out = run_toy(toy_bench, False)
    assert out["correct"] is False
    c = out["compared"]["logit_gap_max"]
    assert c["value"] > c["limit"]


def test_broken_hedge_is_not_correct(tmp_path, monkeypatch):
    """An altered token of the on-device tier, where it is produced; an
    SLA too short for the remote tier makes the hedge answer."""
    import toy
    from chipbench import counts, spec
    from repro.serving import backend

    bench = toy.write(tmp_path, sla_ms=1.0)
    monkeypatch.setattr(spec, "traffic_file", lambda name: tmp_path / f"{name}.json")
    monkeypatch.setattr(counts, "peaks", lambda kind: None)
    original = backend.OnDeviceBackend.generate

    def generate(self, name, tokens, n_steps, greedy=True):
        out, wall = original(self, name, tokens, n_steps, greedy)
        return (out + 1) % self.variants[name].cfg.vocab_size, wall

    monkeypatch.setattr(backend.OnDeviceBackend, "generate", generate)
    out = run_toy(bench, False)
    assert out["correct"] is False
    c = out["compared"]["logit_gap_max"]
    assert c["value"] > c["limit"]


def test_control_is_not_correct(toy_bench):  # noqa: F811
    """The control (the reference one precision step down, in the
    program's place) comes out ``correct`` false through the harness's
    own comparison and limit, where the program's run is correct."""
    sound = run_toy(toy_bench, False)
    control = run_toy(toy_bench, False, control=True)
    assert sound["correct"] is True
    assert control["correct"] is False
    c = control["compared"]["logit_gap_max"]
    assert c["value"] > c["limit"] > sound["compared"]["logit_gap_max"]["value"]
