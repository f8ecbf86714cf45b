"""The traffic generator: seeded, clipped to the grid, bursty as asked, the
same work for every seed, and due times that the server cannot move."""
import json
import math
import types

import numpy as np
import pytest

from checkout import BENCH
from harness import cell
from harness import traffic as T

CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
# a closed document queue; the four-device rehearsal serves it
DOCS = json.loads((BENCH / "tests" / "data" / "tiny_docs.json").read_text())
BIG_SEED = 2 ** 31 + 12345


def cv(x) -> float:
    x = np.asarray(x, float)
    return float(x.std() / x.mean())


@pytest.mark.parametrize("trf", [CHAT, DOCS], ids=["chat", "docs"])
def test_one_seed_gives_the_same_requests(trf):
    a = T.make(trf, BIG_SEED, 20.0, 49152)
    b = T.make(trf, BIG_SEED, 20.0, 49152)
    c = T.make(trf, BIG_SEED + 1, 20.0, 49152)
    key = [(it.due, it.out_len, it.prompt.tobytes()) for it in a]
    assert key == [(it.due, it.out_len, it.prompt.tobytes()) for it in b]
    assert key != [(it.due, it.out_len, it.prompt.tobytes()) for it in c]


@pytest.mark.parametrize("trf", [CHAT, DOCS], ids=["chat", "docs"])
def test_every_seed_gets_the_same_work_in_the_same_order(trf):
    """Seeds change the token ids, not the lengths or the due times."""
    runs = [T.make(trf, s, 30.0, 49152) for s in (1, 7, BIG_SEED)]
    shape = [[(len(it.prompt), it.out_len, it.due) for it in r]
             for r in runs]
    assert shape[0] == shape[1] == shape[2]
    assert runs[0][0].prompt.tobytes() != runs[1][0].prompt.tobytes()
    other = T.make(dict(trf, schedule_seed=trf["schedule_seed"] + 1), 1,
                   30.0, 49152)
    assert [(len(it.prompt), it.out_len, it.due) for it in other] \
        != shape[0]
    if trf["loop"] == "open":
        n = len(runs[0])
        every = T.gaps(trf["rate_per_s"], trf["gap_cv"], n)
        d = np.diff([it.due for it in runs[0]])
        # the gaps, less the one that the first due time (0) absorbs
        assert np.isclose(d[:, None], every[None, :]).any(1).all()


@pytest.mark.parametrize("trf", [CHAT, DOCS], ids=["chat", "docs"])
def test_lengths_are_clipped_and_on_the_grid(trf):
    items = T.make(trf, 3, 60.0, 49152)
    p = np.array([len(it.prompt) for it in items])
    o = np.array([it.out_len for it in items])
    spec = trf["prompt"]
    assert p.min() >= spec["min"] and p.max() <= spec["max"]
    assert np.all(p % spec["grid"] == 0)
    assert set(p) <= set(T.grid(spec))
    assert o.min() >= trf["output"]["min"]
    assert o.max() <= trf["output"]["max"]


def test_chat_grid_and_its_warm_up():
    assert T.grid(CHAT["prompt"]) == [512, 1024, 1536]
    cellf = json.loads((BENCH / "cells" / "granite-8b-1chip.chat.json")
                       .read_text())
    sets = cell.warm_sets(CHAT, cellf)
    assert len(sets) == cellf["n_slots"] * 3
    assert {w for _, w in sets} == set(T.grid(CHAT["prompt"]))


def test_docs_warm_up_is_one_chunk_per_row_count():
    cellf = {"n_slots": 16, "prefill_chunk": 16}
    sets = cell.warm_sets(DOCS, cellf)
    assert sets == [(m, cellf["prefill_chunk"])
                    for m in range(1, cellf["n_slots"] + 1)]
    assert all(len_ % cellf["prefill_chunk"] == 0
               for len_ in T.grid(DOCS["prompt"]))


def test_open_loop_rate_and_burst_cv():
    trf = dict(CHAT, rate_per_s=4.0)
    items = T.make(trf, 5, 2000.0, 100)
    assert len(items) == 8000
    gaps = np.diff([it.due for it in items])
    assert gaps.mean() == pytest.approx(0.25, rel=0.01)
    assert cv(gaps) == pytest.approx(trf["gap_cv"], rel=0.05)
    assert cv(T.gaps(1.0, 1.0, 20000)) == pytest.approx(1.0, rel=0.02)


def test_closed_queue_is_all_due_at_once_in_whole_blocks():
    items = T.make(DOCS, 9, 45.0, 49152)
    assert len(items) == DOCS["block"] * DOCS["blocks"]
    assert all(it.due == 0.0 for it in items)
    b = DOCS["block"]
    first = sorted(len(it.prompt) for it in items[:b])
    assert all(sorted(len(it.prompt) for it in items[k:k + b]) == first
               for k in range(0, len(items), b))


def _request(rid, arrival, start, prompt_len=4):
    return types.SimpleNamespace(rid=rid, arrival=arrival, start_time=start,
                                 prompt=np.zeros(prompt_len, np.int32),
                                 max_new_tokens=3, served=True,
                                 output=[1, 2, 3], finish_time=start + 1)


def test_due_times_are_kept_when_the_server_runs_late():
    """Time to first token counts from the due time: a request admitted
    2 s late whose first token came 0.1 s after admission waited 2.1 s."""
    from harness.taps import Recorder
    rec = Recorder([])
    reqs = [_request(0, 1.0, 3.0), _request(1, 1.5, 3.0)]
    for r in reqs:
        for k, t in enumerate((3.1, 3.2, 3.3)):
            rec._stamp(r.rid, k, t)
    rec._stamp(0, 3, 3.4)         # logits of a token past the last: dropped
    run = types.SimpleNamespace(loop="open", seconds=10.0, setup_s=5.0,
                                recorder=rec, attempted=reqs, failed=[],
                                requests=reqs)
    e2e = cell.end_to_end(run)
    assert e2e["ttft_p95_ms"] == pytest.approx(
        1e3 * (1.6 + 0.95 * (2.1 - 1.6)))
    assert e2e["tpot_p95_ms"] == pytest.approx(100.0)
    assert e2e["output_tok_s"] == pytest.approx(6 / 10.0)


def test_a_failed_request_is_infinitely_late():
    from harness.taps import Recorder
    rec = Recorder([])
    reqs = [_request(i, 0.1 * i, 0.1 * i) for i in range(20)]
    for r in reqs[:-1]:
        for k in range(3):
            rec._stamp(r.rid, k, r.start_time + 0.05 * (k + 1))
    run = types.SimpleNamespace(loop="open", seconds=10.0, setup_s=1.0,
                                recorder=rec, attempted=reqs,
                                failed=[reqs[-1]], requests=reqs)
    assert math.isinf(cell.end_to_end(run)["ttft_p95_ms"])
    acc = cell.account(CHAT, reqs, rec, 10.0)
    assert [r.rid for r in acc["failed"]] == [19]
    assert len(acc["attempted"]) == 20


def test_percentile_and_spread():
    from harness.stats import percentile, spread
    x = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(x, 95) == pytest.approx(np.percentile(x, 95))
    assert math.isinf(percentile(x[:4] + [math.inf], 95))
    assert percentile(x[:4] + [math.inf], 50) == 3.0
    # quartiles 1.5 and 4.5 (statistics' exclusive method), median 3
    assert spread(x) == pytest.approx(1.0)
