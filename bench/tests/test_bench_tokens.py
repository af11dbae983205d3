"""Token traffic (``traffic.token_data``): packed documents from the seed,
next-token targets, the end ids where the document lengths put them,
``padded_round`` on tokens; and a tiny token cohort, added to a
benchmark root as new files alone, through ``harness.Program`` on the
system's unified engine."""
import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(Path(__file__).resolve().parent)]

import harness  # noqa: E402
import registry  # noqa: E402
import tiny  # noqa: E402
import traffic  # noqa: E402

VOCAB = {"vocab_size": 1000}
MIX = {"data": "tokens", "n_train": 512, "seq_len": 511,
       "doc_len_median": 64, "doc_len_sigma": 0.8, "n_topics": 8,
       "topic_vocab": 16, "signal": 0.5, "round_fraction": 0.1,
       "batch_size": 4, "local_epochs": 1}
SEED = 2 ** 31 + 77


def _bytes(data):
    return {k: hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()
            for k, a in data.items()}


def test_same_seed_same_bytes_other_seed_not():
    a = traffic.make_data(MIX, VOCAB, SEED)
    assert _bytes(a) == _bytes(traffic.make_data(MIX, VOCAB, SEED))
    other = _bytes(traffic.make_data(MIX, VOCAB, SEED + 1))
    assert all(other[k] != v for k, v in _bytes(a).items())


def test_next_token_targets_int32_in_vocabulary():
    d = traffic.make_data(MIX, VOCAB, SEED)
    assert list(d) == list(traffic.keys(MIX)) == ["tokens", "labels"]
    x, y = d["tokens"], d["labels"]
    assert x.shape == y.shape == (512, 511)
    assert x.dtype == y.dtype == np.int32
    np.testing.assert_array_equal(y[:, :-1], x[:, 1:])
    # one array of n_train x (seq_len + 1) ids under both views
    assert np.shares_memory(x, y)
    for a in (x, y):
        assert a.min() >= 0 and a.max() < VOCAB["vocab_size"]


@pytest.mark.parametrize("block", [traffic.BLOCK, 1000])
def test_end_ids_where_the_document_lengths_end(monkeypatch, block):
    """Every document ends in ``EOD`` and nothing else is ``EOD``, across
    row and block boundaries."""
    monkeypatch.setattr(traffic, "BLOCK", block)
    d = traffic.make_data(MIX, VOCAB, SEED)
    flat = np.concatenate([d["tokens"], d["labels"][:, -1:]], axis=1).ravel()
    total = MIX["n_train"] * (MIX["seq_len"] + 1)
    ends = np.cumsum(traffic.doc_lengths(MIX, SEED, total))
    assert ends[-2] < total <= ends[-1]
    np.testing.assert_array_equal(np.flatnonzero(flat == traffic.EOD),
                                  ends[ends <= total] - 1)


@pytest.mark.parametrize("sigma", [0.1, 0.8])
def test_document_count_and_median_length(sigma):
    """Ends counted ~ tokens / mean length, the lognormal's mean being
    ``doc_len_median * exp(sigma**2 / 2)`` (the median itself as sigma
    goes to 0); median length ~ ``doc_len_median``; both within 6 %,
    over three standard errors at these ~3,000-4,000 documents."""
    mix = dict(MIX, doc_len_sigma=sigma)
    counts, medians = [], []
    for seed in (SEED, 3):
        d = traffic.make_data(mix, VOCAB, seed)
        flat = np.concatenate([d["tokens"], d["labels"][:, -1:]],
                              axis=1).ravel()
        ends = np.flatnonzero(flat == traffic.EOD)
        counts.append(len(ends))
        medians.append(np.median(np.diff(ends)))
    total = mix["n_train"] * (mix["seq_len"] + 1)
    want = total / (mix["doc_len_median"] * np.exp(sigma ** 2 / 2))
    assert counts == pytest.approx([want] * 2, rel=0.06)
    assert medians == pytest.approx([mix["doc_len_median"]] * 2, rel=0.06)


@pytest.mark.parametrize("signal", [0.0, 1.0])
def test_documents_keep_to_their_topic(signal):
    """At ``signal`` 1 a document's ids are among its topic's
    ``topic_vocab``; at 0 a long document has many more."""
    mix = dict(MIX, signal=signal)
    d = traffic.make_data(mix, VOCAB, SEED)
    flat = np.concatenate([d["tokens"], d["labels"][:, -1:]], axis=1).ravel()
    ends = np.flatnonzero(flat == traffic.EOD)
    docs = [flat[a + 1:b] for a, b in zip(ends[:-1], ends[1:])
            if b - a > 200]
    assert len(docs) > 20
    most = max(len(np.unique(doc)) for doc in docs)
    if signal == 1.0:
        assert most <= mix["topic_vocab"]
    else:
        assert min(len(np.unique(doc)) for doc in docs) > 100


def test_padded_round_on_tokens():
    mix = dict(MIX, batch_size=5)
    d = traffic.make_data(mix, VOCAB, SEED)
    parts = traffic.partition(traffic.n_rows(d), 4, SEED)
    take = traffic.round_take(mix, len(parts[1]))       # 13: 5, 5, 3
    assert take == 13
    x, y, valid = traffic.padded_round(d, mix, parts[1], SEED, 1, 0)
    assert x.shape == y.shape == (3, 5, 511)
    assert x.dtype == y.dtype == np.int32
    assert valid.shape == (3, 5) and valid.dtype == np.float32
    np.testing.assert_array_equal(valid.sum(axis=1), [5, 5, 3])
    assert not x[2, 3:].any() and not y[2, 3:].any()
    rows = np.concatenate(traffic.client_round(mix, parts[1], SEED, 1, 0))
    np.testing.assert_array_equal(x[valid > 0], d["tokens"][rows])
    np.testing.assert_array_equal(y[valid > 0], d["labels"][rows])


def test_unknown_data_kind_named():
    with pytest.raises(ValueError, match="tokens"):
        traffic.make_data(dict(MIX, data="audio"), VOCAB, SEED)


def _digest(bench: Path) -> dict:
    return {str(p.relative_to(bench)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(bench.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_token_cohort_from_new_files_through_the_unified_engine(
        tmp_path, monkeypatch):
    import jax
    from repro.fl import spans as fl_spans
    root = tiny.make_root(tmp_path)
    name = tiny.add_lm_cell(root)
    repo = {k: v for k, v in _digest(BENCH).items()
            if not k.startswith(("tests/", "out/"))}
    got = _digest(root / "bench")
    assert {k: got.get(k) for k in repo} == repo, "an existing file changed"
    assert "families/tinylm.py" in set(got) - set(repo)

    cell = registry.load_cell(name, root)
    batches = []
    real_span = fl_spans.span

    class Recorded:
        def __init__(self, span_name, **counts):
            self.span, self.name = real_span(span_name, **counts), span_name

        def __enter__(self):
            self.span.__enter__()
            return self

        def __exit__(self, *exc):
            return self.span.__exit__(*exc)

        def set_metadata(self, **counts):
            if self.name == fl_spans.BATCHES:
                batches.append(counts)
            self.span.set_metadata(**counts)

    monkeypatch.setattr(fl_spans, "span", Recorded)
    prog = harness.Program(cell, SEED)
    assert prog.backend.name == "unified"
    mix = tiny.LM_MIX
    assert traffic.n_rows(prog.data) == mix["n_train"]
    state = g0 = harness.host_copy(prog.backend.init_state(
        jax.random.PRNGKey(0)))
    for r in range(2):
        state = prog.round(state, r)
    g2 = harness.host_copy(state)
    assert harness.all_finite(g2)
    moved = [float(np.abs(a - b).max()) for a, b in
             zip(jax.tree.leaves(g2), jax.tree.leaves(g0))]
    assert max(moved) > 0
    # every client's round rows, tokens and labels, int32
    n_client = mix["n_train"] // 3
    rows = traffic.round_take(mix, n_client) * mix["local_epochs"]
    hand = 3 * rows * mix["seq_len"] * 4 * 2
    assert [b["bytes"] for b in batches] == [hand, hand]
    assert [b["steps"] for b in batches] == [
        traffic.steps_per_round(mix, n_client)] * 2
    # the round's model FLOPs come from the family module and the mix
    fam = cell.family()
    per = sum(fam.train_flops_per_sample(c, mix)
              for c in fam.client_dicts(cell.config))
    assert harness.model_flops_per_round(cell) == per * rows
    prog.close()
