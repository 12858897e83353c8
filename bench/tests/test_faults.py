"""A run whose timed path is broken underneath reads ``correct: false``:
one case for each fault a one-chip serving cell can have."""

import pytest

from bench import build
from bench.tests import tiny


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return tiny.write(tmp_path_factory.mktemp("bench"))


def _alter_token(engine):
    """A served token is altered where it is produced."""
    step = engine.step

    def faulty():
        out = step()
        for req in engine.slot_req:
            if req is not None and len(req.generated) == 3:
                req.generated[2] = (req.generated[2] + 1) % engine.cfg.vocab_size
        return out

    engine.step = faulty


def _keep_state(engine):
    """The decode step returns the cache it was given."""
    decode = engine._decode_paged
    engine._decode_paged = lambda p, c, *a: (decode(p, c, *a)[0], c)


@pytest.mark.parametrize("fault", [None, _alter_token, _keep_state],
                         ids=["sound", "token_altered", "state_unchanged"])
def test_fault_is_caught(tiny_bench, capsys, monkeypatch, fault):
    make = build.make_engine

    def broken(*a, **kw):
        engine = make(*a, **kw)
        if fault is not None:
            fault(engine)
        return engine

    monkeypatch.setattr(build, "make_engine", broken)
    res = tiny.run(capsys, tiny_bench, seed=11)
    assert res["correct"] is (fault is None)
