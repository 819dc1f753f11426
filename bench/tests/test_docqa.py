"""The docqa cell's own parts: the closed-loop generator, and the
comparison that decides ``correct`` at the Mistral-7B configuration's
smoke sizes (GQA 4:1 and a 48-token window that its prompts outgrow),
through the same harness as a run on the chip: a sound run and the float8
control are judged in ``test_correctness.py`` for every cell; here the
timed path is broken underneath, the window among the ways."""
import pytest

from bench import registry, run
from bench.tests.test_correctness import (_altered_token, _run,
                                          _state_unchanged)

CELL = "mistral7b-s816.docqa"


def _mix(**over):
    mix = registry.traffic("docqa")
    return run.merged(mix, over)


def _gen(mix):
    return registry.generator(mix["generator"]).make(mix, 7, 8)


def test_callers_start_out_of_phase_then_wait_for_their_answers():
    mix = _mix()
    g = _gen(mix)
    n, warm = mix["callers"], mix["warmup_s"]
    starts = [i * warm / n for i in range(n)]
    assert g.next_due() == 0
    first = g.pop_due(0.0)
    assert [s.due for s in first] == [0.0]
    assert g.next_due() == starts[1]
    first += g.pop_due(starts[3])
    assert [s.due for s in first] == starts[:4]
    # a caller sends again only when its request finishes, at that time
    g.on_finish(first[1], starts[3] + 0.5)
    assert g.pop_due(starts[3] + 0.4) == []
    (again,) = g.pop_due(starts[3] + 0.5)
    assert again.due == starts[3] + 0.5 and again.index == len(first)
    assert g.next_due() == starts[4]
    first += g.pop_due(warm)
    assert [s.due for s in first[4:]] == starts[4:]
    assert g.next_due() == float("inf")


def test_step_sequence_is_the_same_for_every_seed(monkeypatch):
    """The seed draws token ids, which change no work: on a clock that
    advances by a fixed time per step, two seeds run the same steps,
    with the same prompt tokens chunked in each."""
    from bench import harness

    class Clock:
        now = 0.0

    def sleep(s):
        Clock.now += s

    monkeypatch.setattr(harness, "CLOCK", lambda: Clock.now)
    monkeypatch.setattr(harness.time, "sleep", sleep)
    seqs = []
    for seed in (11, 12):
        Clock.now = 0.0
        _, conf, mix, _ = run.load_cell(CELL, rehearse=True)
        engine, _ = harness.build(conf, seed)
        real = engine.step

        def step(real=real):
            st = real()
            Clock.now += 0.1 + 0.2 * (st["prefill_tokens"] > 0)
            return st

        engine.step = step
        src = _gen(mix)
        w = harness.drive(engine, src, vocab=conf["model"]["vocab"],
                          seed=seed, warmup_s=mix["warmup_s"], seconds=8.0,
                          counter=harness.CompileCounter(),
                          calls=harness.CallLog(engine))
        seqs.append([(s.prefill_tokens, s.decoded) for s in w.steps])
    assert len(seqs[0]) >= 30 and sum(p for p, _ in seqs[0]) > 0, seqs
    assert seqs[0] == seqs[1], seqs


def test_reference_starts_with_the_program_state_freed(monkeypatch):
    """``check_served`` frees the engine, its weights and arenas before
    the reference runs, so the reference has the device's memory (at
    Mistral-7B's size the program's state leaves it too little)."""
    import gc

    import jax

    from bench import check

    def live():
        gc.collect()
        return sum(a.nbytes for a in jax.live_arrays())

    held = []
    real = check.widest_gaps

    def widest_gaps(*a, **k):
        held.append(live())
        return real(*a, **k)

    monkeypatch.setattr(check, "widest_gaps", widest_gaps)
    before = live()
    wl, conf, mix, _ = run.load_cell(CELL, rehearse=True)
    res = run.serve(wl, conf, mix, 5, 1.0, False, None)
    state = res["weight_bytes"] + check.tree_bytes(
        (res["engine"].pool.k, res["engine"].pool.v))
    assert live() - before >= state
    run.check_served(res, conf, 5)
    assert held and held[0] - before < 0.01 * state, (held, before, state)


def test_sizes_are_the_mix_quantiles_in_send_order():
    mix = _mix()
    g = _gen(mix)
    specs = g.pop_due(1e9)
    for _ in range(40):
        g.on_finish(specs[-1], 1e9)
        specs += g.pop_due(1e9)
    p = [s.prompt_len for s in specs]
    o = [s.out_len for s in specs]
    assert min(p) >= 512 and max(p) <= 3840
    assert min(o) >= 16 and max(o) <= 256
    assert max(a + b for a, b in zip(p, o)) <= 4096
    again = _gen(mix).pop_due(1e9)
    assert [(s.prompt_len, s.out_len) for s in again] == \
        [(s.prompt_len, s.out_len) for s in specs[:len(again)]]


def test_smoke_prompts_outgrow_the_window():
    _, conf, mix, _ = run.load_cell(CELL, rehearse=True)
    assert mix["prompt"]["median"] > conf["model"]["window"]
    m = conf["model"]
    assert m["n_heads"] == 4 * m["n_kv_heads"]


@pytest.mark.parametrize("plant", [_altered_token, _state_unchanged])
def test_broken_timed_path_is_not_correct(plant, monkeypatch):
    res, conf = _run(CELL, 77, plant=plant, monkeypatch=monkeypatch)
    assert not res["correct"], res["reading"]
    assert res["reading"]["widest_gap"] > conf["check"]["widest_gap"]


def test_serving_without_the_window_is_not_correct():
    res, conf = _run(CELL, 78, served={"model": {"window": None}})
    assert not res["correct"], res["reading"]
    assert res["reading"]["widest_gap"] > conf["check"]["widest_gap"]
