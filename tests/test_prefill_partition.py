"""A round's prefill runs at the budget its largest prompt needs (ISSUE 31).

``serve.partition_prefill_round`` decides a round's dispatch groups from
shapes alone:

* a group never runs a budget larger than its largest member needs alone,
  and its rows sum to at most that budget;
* few groups (first-fit over the members in decreasing order of rows);
* cold and warm members never share a group, cold groups go first.

The function is tested as a function; the round is tested on a tiny model
with budgets (256, 1024): what a split or reordered round yields equals,
token for token, what the same prompts yield admitted one a round and what
the solo engine yields.
"""

import dataclasses
import random
import threading

import pytest

from docqa_tpu import obs
from docqa_tpu.config import DecoderConfig, GenerateConfig
from docqa_tpu.engines import serve
from docqa_tpu.engines.generate import GenerateEngine
from docqa_tpu.engines.serve import (
    ContinuousBatcher,
    partition_prefill_round,
)
from docqa_tpu.runtime.metrics import DEFAULT_REGISTRY

SERVED = (512, 4096)  # the budgets of the served shape: {512} | {full}


def _pick(budgets, n):
    return next((t for t in budgets if n <= t), budgets[-1])


def _parent_groups(rows, warm, budgets):
    """The rule this PR replaced: fill a group in arrival order until the
    LARGEST budget is passed, then run the smallest budget that holds the
    sum."""
    out = []
    for flag in (False, True):
        cur = []
        for i, w in enumerate(warm):
            if bool(w) != flag:
                continue
            if cur and sum(rows[j] for j in cur) + rows[i] > budgets[-1]:
                out.append((flag, cur))
                cur = []
            cur.append(i)
        if cur:
            out.append((flag, cur))
    return [(flag, _pick(budgets, sum(rows[j] for j in g)), g)
            for flag, g in out]


def _shape(plan, rows):
    """Budget and the members' rows of every group, in dispatch order."""
    return [(T, [rows[i] for i in members]) for _w, T, members in plan]


# ---- the packer as a function ------------------------------------------------

CASES = [
    # three 323-token prompts: three dispatches of the small program
    ([384] * 3, [(512, [384])] * 3),
    ([256, 256], [(512, [256, 256])]),
    ([384], [(512, [384])]),
    ([640], [(4096, [640])]),
    # a prompt that needs the large budget has paid for it: others ride
    ([640, 384], [(4096, [640, 384])]),
    ([384, 640], [(4096, [640, 384])]),
    ([384, 384, 640], [(4096, [640, 384, 384])]),
    ([384] * 16, [(512, [384])] * 16),
    ([2048, 2048, 384], [(4096, [2048, 2048]), (512, [384])]),
    ([512] * 9, [(512, [512])] * 9),
    ([128, 384, 128, 256, 128], [(512, [384, 128]), (512, [256, 128, 128])]),
]


@pytest.mark.parametrize("rows,want", CASES,
                         ids=["-".join(map(str, c[0][:4])) + f"_x{len(c[0])}"
                              for c in CASES])
def test_partition_cases_at_the_served_budgets(rows, want):
    plan = partition_prefill_round(rows, [False] * len(rows), SERVED)
    assert _shape(plan, rows) == want
    assert sorted(i for _w, _T, m in plan for i in m) == list(range(len(rows)))


@pytest.mark.parametrize("rows", [c[0] for c in CASES if sum(c[0]) <= 4096],
                         ids=lambda r: "-".join(map(str, r[:4])) + f"_x{len(r)}")
def test_one_budget_alone_packs_as_the_parent_did(rows):
    """With the full budget alone there is nothing to choose: a round that
    fits it is one dispatch of it, as before."""
    warm = [False] * len(rows)
    plan = partition_prefill_round(rows, warm, (4096,))
    parent = _parent_groups(rows, warm, (4096,))
    assert [(w, T, sorted(m)) for w, T, m in plan] == parent
    assert len(plan) == 1


def test_cold_and_warm_never_share_a_group_and_cold_goes_first():
    rows = [384, 128, 128, 384, 128, 640]
    warm = [False, True, True, False, True, True]
    plan = partition_prefill_round(rows, warm, SERVED)
    assert [(w, T, m) for w, T, m in plan] == [
        (False, 512, [0]), (False, 512, [3]),
        (True, 4096, [5, 1, 2, 4]),
    ]
    # two prompts that would share one 512-row group apart: not across kinds
    assert _shape(
        partition_prefill_round([256, 256], [False, True], SERVED),
        [256, 256],
    ) == [(512, [256]), (512, [256])]


def test_equal_rows_keep_arrival_order():
    plan = partition_prefill_round([256] * 5, [False] * 5, SERVED)
    assert [m for _w, _T, m in plan] == [[0, 1], [2, 3], [4]]


@pytest.mark.parametrize("seed", range(12))
def test_partition_invariants_over_random_rounds(seed):
    rng = random.Random(seed)
    for _ in range(200):
        budgets = rng.choice(
            [SERVED, (256, 1024), (4096,), (128, 512, 2048), (1024,)]
        )
        n = rng.randint(1, 16)
        unit = 128
        rows = [unit * rng.randint(1, budgets[-1] // unit) for _ in range(n)]
        if rng.random() < 0.5:  # the served case: many short prompts
            rows = [unit * rng.randint(1, 5) for _ in range(n)]
        warm = [rng.random() < 0.3 for _ in range(n)]
        plan = partition_prefill_round(rows, warm, budgets)
        # every entry in exactly one group
        assert sorted(i for _w, _T, m in plan for i in m) == list(range(n))
        kinds = [w for w, _T, _m in plan]
        assert kinds == sorted(kinds), "a warm group ahead of a cold one"
        for w, T, members in plan:
            assert {bool(warm[i]) for i in members} == {w}
            # rule 1: the budget is the one the largest member needs alone
            assert T == _pick(budgets, max(rows[i] for i in members))
            assert sum(rows[i] for i in members) <= T
        # rule 2: no later group of a kind fits whole into an earlier one
        for a in range(len(plan)):
            for b in range(a + 1, len(plan)):
                if plan[a][0] != plan[b][0]:
                    continue
                used = sum(rows[i] for i in plan[a][2])
                assert used + sum(rows[i] for i in plan[b][2]) > plan[a][1]
        # only compiled budgets run
        assert {T for _w, T, _m in plan} <= set(budgets)


# ---- the round ---------------------------------------------------------------

CFG = DecoderConfig(
    vocab_size=128, hidden_dim=64, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=1024,
    dtype="float32",
)
PLAIN = GenerateConfig(
    temperature=0.0, eos_id=2, prefill_token_buckets=(256,),
)
SPEC = dataclasses.replace(PLAIN, speculative_k=4)
COUNTERS = ("serve_admit_rounds", "serve_admitted",
            "serve_prefill_dispatches", "serve_prefill_rounds_split",
            "serve_prefill_tokens", "serve_prefill_budget_tokens")


@pytest.fixture(scope="module")
def engines():
    plain = GenerateEngine(CFG, PLAIN, seed=7)
    return {"plain": plain,
            "spec": GenerateEngine(CFG, SPEC, params=plain.params)}


@pytest.fixture(autouse=True)
def clean_recorder():
    obs.set_enabled(True)
    obs.DEFAULT_RECORDER.clear()
    yield
    obs.set_enabled(True)
    obs.DEFAULT_RECORDER.clear()


def _ctx(n, seed=3):
    return [(seed + i * 7) % 120 + 3 for i in range(n)]


def _counters():
    return {n: DEFAULT_REGISTRY.counter(n).value for n in COUNTERS}


def _gained(before):
    return {n: v - before[n] for n, v in _counters().items()}


def _batcher(engine, **kw):
    b = ContinuousBatcher(engine, n_slots=6, chunk=4, cache_len=1024, **kw)
    assert b._token_buckets == [256, 1024]
    return b


class Gate:
    """Holds the worker inside the first decode dispatch it makes after
    ``arm()``, so that whatever the test submits meanwhile is ONE round when
    the next iteration pops."""

    def __init__(self, monkeypatch, batcher):
        self._armed = False
        self._held = threading.Event()
        self._go = threading.Event()
        run = serve.spine_run

        def spine_run(stage, fn, *a, **kw):
            if (self._armed and stage == "serve_decode"
                    and threading.current_thread() is batcher._worker):
                self._armed = False
                self._held.set()
                assert self._go.wait(120), "the test never released the worker"
            return run(stage, fn, *a, **kw)

        monkeypatch.setattr(serve, "spine_run", spine_run)

    def arm(self):
        self._armed = True

    def wait_held(self):
        assert self._held.wait(120), "the worker never reached its chunk"

    def release(self):
        self._go.set()


def _one_round(b, gate, prompts, keys=None, max_new=10):
    """``prompts`` admitted in ONE round, beside a live lane: per prompt
    (tokens, attributes of its ``serve_prefill`` span, the ``round`` of its
    hold), and what the counters gained over that round alone."""
    keys = keys or [None] * len(prompts)
    gate.arm()
    live = b.submit_ids(_ctx(40, seed=29), max_new_tokens=24)
    gate.wait_held()
    before = _counters()
    done = []
    for i, (p, key) in enumerate(zip(prompts, keys)):
        ctx = obs.new_trace(f"round{i}")
        with ctx.activate():
            done.append((ctx, b.submit_ids(
                p, max_new_tokens=max_new, prefix_key=key
            )))
    gate.release()
    out = []
    for ctx, h in done:
        tokens = h.result(timeout=300)
        obs.finish(ctx)
        spans = {s.name: s for s in ctx.trace.snapshot_spans()}
        out.append((tokens, dict(spans["serve_prefill"].attrs),
                    spans["serve_admit_hold"].attrs["round"]))
    live.result(timeout=300)
    return out, _gained(before)


@pytest.mark.parametrize("kind", ["plain", "spec"])
def test_a_split_round_matches_one_a_round_and_solo(
    engines, monkeypatch, kind
):
    """Three prompts of 256 rows each: one 1024-row dispatch under the old
    rule, three 256-row dispatches now, and the same tokens."""
    solo = engines["plain"]
    prompts = [_ctx(150), _ctx(170, seed=11), _ctx(190, seed=17)]
    want = [solo.generate_ids([p], max_new_tokens=10)[0] for p in prompts]
    b = _batcher(engines[kind], prefix_cache=False)
    try:
        b.warmup()
        compiled = b._prefill_fn._cache_size()
        assert compiled == len(b._token_buckets)
        one_a_round = [
            b.submit_ids(p, max_new_tokens=10).result(timeout=300)
            for p in prompts
        ]
        got, gained = _one_round(b, Gate(monkeypatch, b), prompts)
        # no new shape of the prefill program: zero retraces
        assert b._prefill_fn._cache_size() == compiled
    finally:
        b.stop()
    assert [t for t, _a, _r in got] == one_a_round == want
    assert [r for _t, _a, r in got] == [3, 3, 3]
    assert gained["serve_admit_rounds"] == 1
    assert gained["serve_admitted"] == 3
    assert gained["serve_prefill_dispatches"] == 3
    assert gained["serve_prefill_rounds_split"] == 1
    assert gained["serve_prefill_budget_tokens"] == 3 * 256
    assert gained["serve_prefill_tokens"] == 150 + 170 + 190
    assert sorted(a["dispatch"] for _t, a, _r in got) == [0, 1, 2]
    for _t, attrs, _r in got:
        assert attrs["dispatches"] == 3 and attrs["batch"] == 3
        assert attrs["budget_tokens"] == 256
        assert attrs["packed_tokens"] <= attrs["budget_tokens"]
    assert b._alloc.blocks_in_use == 0


@pytest.mark.parametrize("case,lengths,dispatch_of,budgets", [
    # the long one opens the full-budget group, the short ones ride along
    # behind it: ONE dispatch, lanes in another order than the slots
    ("ride_along", (100, 300, 150), (0, 0, 0), (1024, 1024, 1024)),
    # three groups, dispatched largest first: arrival order a, b, c runs
    # as b, c, a
    ("largest_first", (100, 150, 200), (2, 0, 1), (256, 256, 256)),
    # two fill one small group, the third takes a second
    ("pairs", (60, 200, 100), (1, 0, 1), (256, 256, 256)),
])
def test_reordered_groups_give_each_request_its_own_first_token(
    engines, monkeypatch, case, lengths, dispatch_of, budgets
):
    solo = engines["plain"]
    prompts = [_ctx(n, seed=5 + 6 * i) for i, n in enumerate(lengths)]
    want = [solo.generate_ids([p], max_new_tokens=10)[0] for p in prompts]
    assert len({w[0] for w in want}) > 1, "first tokens must tell lanes apart"
    b = _batcher(engines["plain"], prefix_cache=False)
    try:
        got, gained = _one_round(b, Gate(monkeypatch, b), prompts)
    finally:
        b.stop()
    assert [t for t, _a, _r in got] == want
    assert tuple(a["dispatch"] for _t, a, _r in got) == dispatch_of
    assert tuple(a["budget_tokens"] for _t, a, _r in got) == budgets
    n_groups = len(set(dispatch_of))
    assert gained["serve_prefill_dispatches"] == n_groups
    assert gained["serve_prefill_rounds_split"] == int(n_groups > 1)
    assert all(a["dispatches"] == n_groups for _t, a, _r in got)
    assert b._alloc.blocks_in_use == 0


@pytest.mark.parametrize("kind", ["plain", "spec"])
def test_warm_and_cold_groups_of_a_split_round_match_solo(
    engines, monkeypatch, kind
):
    """Three cold prompts (three small dispatches where the old rule ran
    one large one), a lane that hits a prefix cached in an earlier round
    and one that shares, in-round, the rows a cold lane of this very round
    writes: every cold group goes ahead of the warm one."""
    solo = engines["plain"]
    seeded, fresh = _ctx(260, seed=11), _ctx(200, seed=19)
    prompts = [
        seeded + [11, 12],       # warm: 256 tokens cached by an earlier round
        _ctx(150),               # cold, 256 rows
        fresh + [9, 4, 7],       # cold, 256 rows; caches its first 128
        fresh + [8],             # warm in-round: shares the rows above
        _ctx(170, seed=17),      # cold, 256 rows
    ]
    keys = ["s", None, "f", "f", None]
    want = [solo.generate_ids([p], max_new_tokens=10)[0] for p in prompts]
    b = _batcher(engines[kind])
    try:
        b.submit_ids(seeded + [10], max_new_tokens=4, prefix_key="s").result(
            timeout=300
        )
        got, gained = _one_round(b, Gate(monkeypatch, b), prompts, keys)
    finally:
        b.stop()
    assert [t for t, _a, _r in got] == want
    attrs = [a for _t, a, _r in got]
    assert [a["shared_tokens"] for a in attrs] == [256, 0, 0, 128, 0]
    assert [a["dispatch"] for a in attrs] == [3, 0, 1, 3, 2]
    assert {a["budget_tokens"] for a in attrs} == {256}
    assert gained["serve_prefill_dispatches"] == 4
    assert gained["serve_prefill_rounds_split"] == 1
    assert gained["serve_prefill_budget_tokens"] == 4 * 256
    assert b._alloc.blocks_in_use == 0


def test_a_warm_lane_of_the_round_is_not_a_prefix_source_in_it(
    engines, monkeypatch
):
    """Warm groups dispatch in the packer's order, not in arrival order, so
    a lane must not read rows that a WARM lane of the same round writes: a
    warm lane lengthens its key's entry only after the round is packed."""
    solo = engines["plain"]
    base = _ctx(260, seed=11)
    longer = base + _ctx(140, seed=23)            # 400 tokens, shares 256
    prompts = [longer, longer[:390] + [5, 6, 7]]  # matches 390 of them
    want = [solo.generate_ids([p], max_new_tokens=10)[0] for p in prompts]
    b = _batcher(engines["plain"])
    try:
        b.submit_ids(base + [10], max_new_tokens=4, prefix_key="s").result(
            timeout=300
        )
        got, _gained_ = _one_round(
            b, Gate(monkeypatch, b), prompts, ["s", "s"]
        )
        # ... and from the next round on the longer entry serves
        assert b._prefix_cache.peek("s", longer) == 384
        p_later = longer[:395] + [9]
        later = b.submit_ids(p_later, max_new_tokens=10, prefix_key="s")
        assert later.result(timeout=300) == solo.generate_ids(
            [p_later], max_new_tokens=10
        )[0]
    finally:
        b.stop()
    assert [t for t, _a, _r in got] == want
    assert [a["shared_tokens"] for _t, a, _r in got] == [256, 256]
    assert b._alloc.blocks_in_use == 0
