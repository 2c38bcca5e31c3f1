"""``serve-chat-steady`` is held to its contract from the files alone
(PR 67, ``ROADMAP.md`` B2 (4)): the window holds 120 requests or more,
so that a p90 has a dozen beyond it; the rate is a stated share, 0.5
to 0.8, of a knee that ``rate_why`` names; ``BENCHMARK.json`` says the
same share. The generator that has to keep that rate starts a client's
thread ahead of its due instant. Nothing else of the traffic moved with
the rate (``test_rate_blocks.py`` holds the file to its sum). Nothing
here is a measurement."""

import functools
import os
import re
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from cells import REPO, bench_json  # noqa: E402

sys.path.insert(0, REPO)
from benchmark import serve_cell, spec, traffic_gen  # noqa: E402

CELL, VOCAB = "serve-chat-steady", 32768
SHARES = (0.8, 0.65, 0.5)  # the issue's order: the highest that resolves
# "<share> x the knee of <knee> requests/s", as rate_why writes it.
SHARE_OF_KNEE = re.compile(
    r"\b(0\.\d+) x the knee of (\d+(?:\.\d+)?) requests/s")

bench = functools.lru_cache(maxsize=None)(bench_json)


@functools.lru_cache(maxsize=None)
def chat() -> dict:
    """The cell's traffic file, as the harness finds it."""
    return spec.load_cell(CELL).traffic


def window(seed: int = 0) -> list:
    return traffic_gen.open_poisson(chat(), float(bench()["run_seconds"]),
                                    seed, VOCAB)


def share_and_knee() -> tuple:
    found = SHARE_OF_KNEE.search(chat()["rate_why"])
    assert found, chat()["rate_why"]
    return float(found.group(1)), float(found.group(2))


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_the_window_holds_120_requests_or_more(seed):
    requests = window(seed)
    assert len(requests) >= 120
    assert len(requests) == round(chat()["rate_per_s"]
                                  * bench()["run_seconds"])
    # Ten or more beyond the p90 (choosing-metrics, section 1).
    assert len(requests) - int(0.9 * len(requests)) >= 12
    assert all(0 <= r.due_s < bench()["run_seconds"] for r in requests)


def test_rate_why_names_a_knee_and_a_share_whose_product_is_the_rate():
    share, knee = share_and_knee()
    assert share in SHARES
    # To two digits.
    assert f"{share * knee:.2g}" == f"{chat()['rate_per_s']:.2g}"
    # When and on which commit it was found.
    assert re.search(r"PR 67", chat()["rate_why"])
    assert re.search(r"\b[0-9a-f]{7}\b", chat()["rate_why"])
    assert "knee_sweep.py" in chat()["rate_why"]


def test_the_cells_why_names_the_same_share():
    share, _ = share_and_knee()
    why = next(w["why"] for w in bench()["workloads"] if w["name"] == CELL)
    assert f"{share} x the knee" in why
    assert str(len(window())) in why  # requests a window
    assert len(why) <= 200 and "\n" not in why and "\t" not in why


def test_the_rehearsal_keeps_its_own_rate():
    assert chat()["rehearsal"]["rate_per_s"] == 4.0
    rehearsed = spec.rehearsed(chat(), True)
    assert rehearsed["rate_per_s"] == 4.0
    assert rehearsed["schedule_seed"] == 22


def test_the_schedule_is_one_replayed_trace_at_the_new_rate():
    a, b = window(1), window(2)
    assert [(r.due_s, len(r.tokens), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.tokens), r.max_new_tokens) for r in b]
    assert [r.tokens for r in a] != [r.tokens for r in b]
    # The request is the file's whatever the rate: 1.86 chunks of 128
    # and 76 answer tokens on average (ISSUE 67 counted them at 1.0/s).
    chunks = [-(-len(r.tokens) // 128) for r in a]
    assert sum(chunks) / len(a) == pytest.approx(1.86, abs=0.02)
    assert sum(r.max_new_tokens for r in a) / len(a) == \
        pytest.approx(76.0, abs=0.5)


def test_the_cell_keeps_its_two_tails_and_their_bounds():
    bounds = {m["name"]: m["bound"] for m in bench()["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert bounds == {"ttft_p90_ms": 0.1, "token_gap_p95_ms": 0.04}
    assert bench()["run_seconds"] == 50


# ------------------------------------------- the generator keeps the rate

class Answers:
    """A handle whose stream yields the request's tokens, the first
    request's only after ``first_takes_s``."""

    def __init__(self, first_takes_s: float = 0.0):
        self.first_takes_s, self.calls = first_takes_s, 0

    def options(self, stream):
        return self

    @property
    def generate(self):
        return self

    def remote(self, payload):
        self.calls += 1
        if self.calls == 1:
            time.sleep(self.first_takes_s)
        return iter(range(payload["max_new_tokens"]))


def due_at(*instants) -> list:
    return [traffic_gen.Request(i, s, [1, 2, 3], 4, 0.0)
            for i, s in enumerate(instants)]


@pytest.mark.parametrize("dues", [(0.4,), (0.4, 0.41, 0.42), (0.0, 0.6)])
def test_a_client_is_started_ahead_and_sends_at_its_due_instant(dues):
    """The thread that sends a request exists ``LEAD_S`` before the
    request is due and sleeps to that instant itself: no thread is
    started on the path from the due instant to the send."""
    clients = serve_cell.Clients(Answers())
    lead = clients.LEAD_S
    assert 0.1 <= lead <= 1.0
    opened = time.perf_counter() + 0.05
    clients.open_loop(due_at(*dues), opened)
    late = [r for r in clients.records if r.request.due_s > lead]
    # Halfway through the lead of the late ones: their threads wait.
    serve_cell.sleep_until(opened + late[0].request.due_s - lead / 2)
    assert all(r.sent == 0.0 for r in late)
    assert len(clients.threads) == 1 + len(clients.records)
    assert clients.join(10.0) == 0
    assert all(r.finished and r.tokens == [0, 1, 2, 3]
               for r in clients.records)
    # Never early; the bound on lateness is the sandbox's, not the
    # chip's (generator_lateness_p95_ms is read there).
    assert all(0.0 <= r.sent - r.due < 0.2 for r in clients.records)


def test_a_request_leaves_on_time_whatever_became_of_the_earlier_one():
    clients = serve_cell.Clients(Answers(first_takes_s=0.5))
    opened = time.perf_counter() + 0.05
    clients.open_loop(due_at(0.0, 0.1), opened)
    assert clients.join(10.0) == 0
    first, second = clients.records
    assert second.arrivals[-1] < first.arrivals[0]
    assert 0.0 <= second.sent - second.due < 0.2


@pytest.mark.parametrize("dues", [(0.5,), (0.2, 5.0)])
def test_closing_ends_the_clients_that_wait_for_their_instant(dues):
    """A client asleep before its due instant sends nothing once the
    cell is closing, and the dispatcher starts no more."""
    handle = Answers()
    clients = serve_cell.Clients(handle)
    opened = time.perf_counter()
    clients.open_loop(due_at(*dues), opened)
    serve_cell.sleep_until(opened + dues[0] - clients.LEAD_S / 2)
    clients.closing.set()
    assert clients.join(10.0) == 0
    assert time.perf_counter() - opened < dues[0] + 1.0
    assert handle.calls == 0 and not any(r.sent for r in clients.records)
