"""The run with the timed path broken underneath: the harness skips only
its look for a chip, and ``correct`` comes out false for each fault the
cell can have. The control (the reference in float8, put in the
program's place) fails a limit too."""
import pytest

import chip_bench_support as sup


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    return sup.reduced_copy(tmp_path_factory.mktemp("faults"))


def _alter_second_token(monkeypatch):
    from repro.serve import engine as eng

    real = eng.DecodeEngine.step

    def step(self, params):
        out = real(self, params)
        vocab = self.model.cfg.vocab_size
        for lane in self._lanes:
            if lane is not None and len(lane.generated) == 2:
                lane.generated[-1] = (lane.generated[-1] + 1) % vocab
                lane.current = lane.generated[-1]
        return out

    monkeypatch.setattr(eng.DecodeEngine, "step", step)


def _skip_kv_write(monkeypatch):
    from repro.models import layers

    monkeypatch.setattr(layers, "_paged_write", lambda pages, new, rows: pages)


@pytest.mark.parametrize("fault", [_alter_second_token, _skip_kv_write])
@pytest.mark.parametrize("cell", ["qwen3-4b.chat", "qwen3-4b.decode"])
def test_serving_fault_is_not_correct(base, cell, fault, monkeypatch):
    fault(monkeypatch)
    assert not sup.run(base, cell)["correct"]


def _state_unchanged(monkeypatch):
    from repro.train import loop

    real = loop.make_jitted_step

    def make(*a, **k):
        jitted, sh = real(*a, **k)
        return (lambda state, batch: (state, jitted(state, batch)[1])), sh

    monkeypatch.setattr(loop, "make_jitted_step", make)


def _half_batch(monkeypatch):
    from repro.train import loop

    real = loop.make_jitted_step

    def make(*a, **k):
        jitted, sh = real(*a, **k)

        def step(state, batch):
            return jitted(state, {k: v[: len(v) // 2] for k, v in batch.items()})

        return step, sh

    monkeypatch.setattr(loop, "make_jitted_step", make)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_training_fault_is_not_correct(base, fault, monkeypatch):
    fault(monkeypatch)
    result = sup.run(base, "xlstm-350m.train")
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("cell", ["qwen3-4b.chat", "qwen3-4b.decode"])
def test_serving_control_fails_its_limit(base, cell):
    result = sup.run(base, cell, control=True)
    assert result["program_correct"], result["program_checks"]
    assert not result["correct"], result["checks"]


def test_training_control_fails_a_limit(base):
    result = sup.run(base, "xlstm-350m.train", control=True)
    assert result["program_correct"], result["program_checks"]
    assert not result["correct"], result["checks"]
