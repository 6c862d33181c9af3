"""The lower-precision control, put in the program's place at the tiny
size, comes out not correct through the harness's own comparison; the
served-token comparison reads 0 for the reference's own tokens and flags
one altered token."""
import numpy as np

import tiny_cell


def test_control_reads_above_the_limits(monkeypatch, capsys):
    res, _ = tiny_cell.run_tiny(monkeypatch, capsys, control=1)
    assert res["correct"] is False
    c = res["checks"]["route_mismatch"]
    assert c["value"] > c["limit"]


def test_closed_loop_cascade_cell_runs_correct(monkeypatch, capsys):
    res, _ = tiny_cell.run_tiny(monkeypatch, capsys,
                                name="qwen2.5-3b-cascade.lmsys-resend")
    assert res["correct"] is True
    assert res["attempted"] > 8
    assert set(res["metrics"]) == {"latency_p50_ms", "latency_p90_ms",
                                   "output_tokens_per_s", "setup_s"}


def test_served_gap_is_zero_for_reference_tokens_and_flags_an_altered_one():
    from bench import check, weights
    from bench.reference import dense as ref_lm
    from repro.models.model import build_model

    cfg = tiny_cell.tiny_model_config()
    w = weights.make(build_model(cfg).init, tiny_cell.SEED)
    m = {k: tiny_cell.TINY_MODEL[k] for k in (
        "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
        "rope_theta", "vocab_size", "tie_word_embeddings")}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(5, 512, n).tolist() for n in (7, 19)]
    rows = []
    for p in prompts:
        served = []
        for _ in range(6):      # the reference's own greedy tokens
            (_, arg), = ref_lm.served_gaps(w, [(p + served, [0])], m)
            served.append(int(arg[0]))
        rows.append({"kind": "miss", "prompt": p, "served": served})
    assert check.logit_gaps(w, rows, tiny_cell.TINY_MODEL, "dense")[
        "gen_logit_gap"] < 1e-5
    rows[1]["served"][3] = (rows[1]["served"][3] + 1) % 512
    assert check.logit_gaps(w, rows, tiny_cell.TINY_MODEL, "dense")[
        "gen_logit_gap"] > 0.01
