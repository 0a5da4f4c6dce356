"""The check that decides ``correct`` fails a broken timed path: a run on
the CPU with the chip check skipped and the path broken underneath, once
for each fault the cells can have, and once with the lower-precision
control in the program's place."""
from bench import harness
from repro.serving.engine import ServingEngine

from .conftest import TINY_CONFIG


def run(root, **kw):
    return harness.run_cell(root, "tiny.load", 31, 1.0, False,
                            require_tpu=False, log=lambda m: None, **kw)


def test_the_unbroken_path_is_correct(tiny_root):
    assert run(tiny_root)["correct"]


def test_the_fp8_control_is_not_correct(tiny_root):
    r = run(tiny_root, control="fp8")
    assert not r["correct"]
    assert r["check"]["max_rel_err"]["value"] > r["check"]["max_rel_err"]["limit"]


def test_a_stage_that_returns_its_state_unchanged(tiny_root, monkeypatch):
    conv_chain = harness.family(tiny_root, TINY_CONFIG)
    build = conv_chain.build_models

    def broken(config, params, control):
        models = build(config, params, control)
        m = models[1]     # mnasnet stand-in: stage 3 keeps 8 channels, stride 1
        segs = list(m.segments)
        segs[3] = lambda p, x: x
        models[1] = type(m)(m.name, tuple(segs), m.params, m.make_input)
        return models

    monkeypatch.setattr(conv_chain, "build_models", broken)
    assert not run(tiny_root)["correct"]


def test_an_answer_altered_where_it_is_produced(tiny_root, monkeypatch):
    finish, calls = ServingEngine._finish, []

    def altered(self, model_idx, out, *a, **kw):
        calls.append(1)
        if len(calls) == 20 and out is not None:
            out = out * 1.1
        return finish(self, model_idx, out, *a, **kw)

    monkeypatch.setattr(ServingEngine, "_finish", altered)
    r = run(tiny_root)
    assert not r["correct"]
    assert r["check"]["errored"]["value"] == 0


def test_the_exchange_at_the_cut_altered(tiny_root, monkeypatch):
    dispatch = ServingEngine._dispatch_suffix

    def altered(self, model_idx, x, p, *a, **kw):
        if p > 0:
            x = x * 0.5
        return dispatch(self, model_idx, x, p, *a, **kw)

    monkeypatch.setattr(ServingEngine, "_dispatch_suffix", altered)
    assert not run(tiny_root)["correct"]


def test_half_of_the_requests_left_out(tiny_root, monkeypatch):
    drain = ServingEngine.drain

    def halved(self, timeout=60.0):
        out = drain(self, timeout)
        return out[::2]

    monkeypatch.setattr(ServingEngine, "drain", halved)
    r = run(tiny_root)
    assert not r["correct"]
    assert r["check"]["missing"]["value"] > 0


def test_outputs_swapped_between_requests(tiny_root, monkeypatch):
    drain = ServingEngine.drain

    def swapped(self, timeout=60.0):
        out = drain(self, timeout)
        if len(out) > 10:
            mine = [r for r in out if r.model_idx == 0 and r.ok]
            by_time = sorted(mine, key=lambda r: r.submit_time)
            outs = [r.output for r in by_time]
            for r, o in zip(by_time, outs[1:] + outs[:1]):
                r.output = o
        return out

    monkeypatch.setattr(ServingEngine, "drain", swapped)
    assert not run(tiny_root)["correct"]
