"""Hold a server's replies against the plain training forward.

    python scripts/serve_reference_check.py <checkpoint_dir> <replies.json>

``replies.json`` is ``[{"prompt": [...], "tokens": [...]}, ...]`` — what
``POST /v1/generate`` was sent and what it returned at temperature 0.  This
loads the same checkpoint the server loaded
(``train.load_trial_from_checkpoint``), runs the TRAINING forward
(``model.apply``: one full-sequence pass, the trial's own attention) over
prompt + returned tokens, and accepts a reply only if every returned token
is the forward's top-1 at its position or within ``MARGIN`` logits of it.

Exact token equality is not the test: the server computes the same bf16
model by other programs (padded prefill, paged single-token decode), and a
few steps from a random init the top logits lie closer together than bf16
rounding (measured on a v5e at the const.yaml widths: 52 of 56 tokens
exact, largest gap 0.025 logits).  The step-for-step logits parity stays with
``tests/test_serving.py``; this is the end-to-end check ``chip_smoke.py``
runs on the chip after the server has exited (one process per chip).

Prints one ``RESULT {json}`` line; exits 1 when a reply fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: how far (in logits) a returned token may trail the forward's top-1
MARGIN = 0.1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("checkpoint")
    ap.add_argument("replies")
    args = ap.parse_args()

    import logging

    import jax
    import numpy as np

    from determined_tpu import train
    from determined_tpu.utils.chip import device_facts

    logging.basicConfig(level=logging.INFO)
    sys.path.insert(0, os.getcwd())  # the checkpoint's trial class, as `dtpu serve` finds it
    with open(args.replies) as f:
        replies = json.load(f)
    _trial, trainer = train.load_trial_from_checkpoint(args.checkpoint)
    params = trainer.state.params
    trainer.state = trainer.state.replace(opt_state=None)  # only the weights are needed

    # one batch, one compile: every sequence padded to the same multiple of
    # 128 (causal attention: padding after a position cannot reach it)
    seqs = [r["prompt"] + r["tokens"] for r in replies]
    width = -(-max(len(s) for s in seqs) // 128) * 128
    tokens = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, : len(s)] = s
    with trainer.mesh:
        logits = np.asarray(jax.jit(trainer.model.apply)(params, tokens))
    if not np.isfinite(logits[:, : max(len(s) for s in seqs)]).all():
        print("RESULT " + json.dumps({"ok": False, "error": "non-finite logits"}))
        return 1

    checked = exact = 0
    worst = 0.0
    failures = []
    for i, r in enumerate(replies):
        start = len(r["prompt"])
        for j, tok in enumerate(r["tokens"]):
            row = logits[i, start + j - 1]  # position p predicts token p + 1
            gap = float(row.max() - row[tok])
            checked += 1
            exact += int(gap == 0.0)
            worst = max(worst, gap)
            if gap > MARGIN:
                failures.append(
                    {"reply": i, "token_index": j, "token": tok,
                     "forward_top1": int(row.argmax()), "gap": round(gap, 4)}
                )
    result = {
        "ok": not failures,
        "replies": len(replies),
        "tokens_checked": checked,
        "exact_top1": exact,
        "max_gap": round(worst, 4),
        "margin": MARGIN,
        "failures": failures[:8],
        "device": device_facts(),
    }
    print("RESULT " + json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
