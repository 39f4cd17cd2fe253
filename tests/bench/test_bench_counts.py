"""The operation and byte counters against counts made by hand."""
import _tiny_cells  # noqa: F401  (puts the repository on the path)

from bench import counts

S = counts.Shape(layers=2, d=8, heads=4, kv_heads=2, head_dim=2, ffn=16, vocab=32)
KV = 2 * 2 * 2 * 2 * 2          # k and v, 2 layers, 2 kv heads, head_dim 2, bf16


def test_params_by_hand():
    # per layer: wq 8*4*2 + wo 4*2*8 + wk, wv 8*2*2 each + 3 * 8*16
    assert S.layer_matmul_params == 64 + 64 + 32 + 32 + 384
    # blocks + two norms a layer + final norm + tied embedding
    assert S.params == 2 * (576 + 16) + 8 + 32 * 8
    assert S.kv_bytes_per_token() == KV


def test_causal_contexts():
    assert counts.causal_contexts(0, 3) == 1 + 2 + 3
    assert counts.causal_contexts(5, 2) == 6 + 7


def test_train_flops_per_token():
    seq = 4
    fwd = 2 * (2 * 576 + 32 * 8) * seq + 4 * 4 * 2 * (1 + 2 + 3 + 4) * 2
    assert counts.train_flops_per_token(S, seq) == 3 * fwd / seq


def test_prefill_chunk_counts_real_width():
    f, b = counts.prefill_chunk(S, 3, 5, weight_bytes=1000)
    ctx = 4 + 5 + 6 + 7 + 8
    assert f == 2 * 2 * 576 * 5 + 2 * 32 * 8 + 4 * 4 * 2 * ctx * 2
    assert b == 1000 + KV * (3 + 5)


def test_decode_bytes_count_live_lengths_only():
    f, b = counts.decode_step(S, [5, 9], weight_bytes=1000)
    assert b == 1000 + KV * (5 + 9) + KV * 2
    assert f == 2 * (2 * 576 + 256) * 2 + 4 * 4 * 2 * (6 + 10) * 2
    # an idle lane adds nothing: the count knows only the live slots
    assert counts.decode_step(S, [5, 9], weight_bytes=1000) == (f, b)


def test_decode_work_does_not_depend_on_view_len():
    """The same requests served with two pool views (max_len 128 and 256)
    give the same needed work, step for step."""
    from bench.run import execute

    works = []
    for max_len in (128, 256):
        cell = _tiny_cells.serve_cell("serve-phi4mini-docs", max_len=max_len)
        box = {}

        def keep(run_rec, box=box):
            box["work"] = run_rec["serve"]["work"]

        execute(cell, _tiny_cells.env(seconds=1.5, hooks={"run_record": keep}))
        works.append(box["work"])
    n = min(len(works[0]["decode"]), len(works[1]["decode"]))
    assert n > 5
    assert works[0]["decode"][:n] == works[1]["decode"][:n]
    m = min(len(works[0]["prefill"]), len(works[1]["prefill"]))
    assert works[0]["prefill"][:m] == works[1]["prefill"][:m]
