"""The control, the reference put in the program's place in a lower
precision, comes out as not correct under the cells' limits."""
import _tiny_cells
import jax

from bench import traffic
from bench.check import serve as scheck
from bench.check import train as tcheck
from bench.check.judge import judge

SEED = 2**31 + 99


def test_training_control_fails_the_limits():
    cell = _tiny_cells.train_cell()
    fam, m = cell.family(), cell.config["model"]
    opt, job = cell.config["optimizer"], cell.traffic
    batches = [traffic.train_batch(job, m["vocab_size"], SEED, i) for i in range(3)]

    def p0():
        return fam.init(m, SEED, "float32")

    ref = tcheck.reference(fam, m, opt, p0, batches)
    ctl = tcheck.reference(fam, m, opt, p0, batches, cast=fam.fp8_cast)
    ok, compared = judge(tcheck.compare(ctl, ref), cell.limits)
    assert not ok, compared


def test_serving_control_fails_the_limits():
    cell = _tiny_cells.serve_cell("serve-phi4mini-docs")
    assert cell.limits == _tiny_cells.manifest.load_cell("serve-phi4mini-docs").limits
    fam, m = cell.family(), cell.config["model"]
    V = m["vocab_size"]
    params = fam.init(m, SEED, "bfloat16")
    cases = []
    for rid in range(3):
        prompt = traffic.prompt_tokens(SEED, rid, 40, V)
        # greedy continuation by the reference itself: every served token is
        # the reference's best, so only the control's own choice can differ
        seq = list(prompt)
        for _ in range(12):
            h = fam.hidden(m, params, jax.numpy.asarray([seq]))
            seq.append(int(jax.numpy.argmax(fam.logits_at(m, params, h[0, -1]))))
        cases.append((prompt, seq[len(prompt):]))
    ctl = scheck.widest_gap(fam, m, params, cases, 128, 40, control=True)
    ok, compared = judge({"logit_gap": ctl}, cell.limits)
    assert not ok, compared
