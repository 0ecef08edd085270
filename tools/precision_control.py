#!/usr/bin/env python
"""The precision control of a serving cell's reference check: what the
runner's own comparison says of a system that computes in the nearest
precision BELOW the one the configuration states.  It has to say "not
correct"; a check that passes the control cannot tell the stated
precision from a worse one.

    chiprun -- python tools/precision_control.py \\
        --workload glm-4.7-flash-d7.docqa-closed --seed 2147484301 [--rehearse]

(or `--workload trinity-mini-d8.mixed-closed`, or
`--workload granite-4.0-h-small-d10.chat-closed`: any cell whose runner
has `_load`, `verdict` and the check's prompt lengths)

A control system is the cell's plain reference computing lower: with
every matrix rounded to float8 (e4m3; the configurations state
bfloat16), and, where the runner's `CONTROLS` names it, with the
recurrent state of its state-space layers rounded to bfloat16 after
every token (the configuration states float32).  At each of the last
`check.new_tokens` positions of the check's prompts (seeded as the
runner seeds them, the document question behind seeded tokens of the
document's length) a control "serves" its argmax.  The float32
reference reads those tokens as it reads the engine's,
`reference_readings()` by another road, and the runner's `verdict()`
judges them by the configuration's limits.  The last line is a JSON
object with a verdict a control; exit code 0 when every control comes
out not correct, 1 when one passes.
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import manifest  # noqa: E402
from benchmarks.run import _merge  # noqa: E402


def _round_matrices_in_place(tree, dtype):
    """A leaf at a time: two copies of the weights do not fit a chip."""
    for key, value in (tree.items() if isinstance(tree, dict)
                       else enumerate(tree)):
        if isinstance(value, (dict, list)):
            _round_matrices_in_place(value, dtype)
        elif value.ndim >= 2:
            tree[key] = value.astype(dtype).astype(value.dtype)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="the cell's tiny preset on the CPU")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax.numpy as jnp

    cell = manifest.Cell(manifest.load(manifest.ROOT), args.workload,
                         manifest.ROOT)
    config, traffic = cell.config, cell.traffic
    if args.rehearse:
        preset = dict(config["rehearsal"])
        traffic = _merge(traffic, preset.pop("traffic", {}),
                         only_existing=True)
        config = _merge(config, preset)
    runner = cell.module("runners", config["runner"])
    reference = cell.module("reference", config["reference"])
    check, builder = config["check"], config["builder"]
    model = runner._load(builder["model"])(**builder["model_args"],
                                           seed=args.seed)
    params, n_new = model.decode_params(), int(check["new_tokens"])
    rng = np.random.default_rng([args.seed, 0xC0DE])
    # the check's prompt lengths: the runner's own list where it has one
    # (`check_lengths`), else `serve_model.py`'s plain prompts and its
    # question behind one of the traffic's documents
    lengths = runner.check_lengths(check, traffic) if hasattr(
        runner, "check_lengths") else [
            int(n) for n in check["prompt_tokens"]] + [
                int(traffic["prefix"]["tokens"])
                + int(check["document_question_tokens"])]
    # prompt + the tokens the positions are read behind
    texts = [rng.integers(0, model.vocab_size, n + n_new - 1).tolist()
             for n in lengths]
    full = []
    for text in texts:
        margins = []
        logits = np.asarray(reference.next_token_logits(
            params, text, builder["model_args"], n_new, margins))
        full.append((logits, np.min([np.asarray(m)[-n_new:]
                                     for m in margins], axis=0)))
        print(f"float32 reference over {len(text)} tokens", flush=True)
    # the float32 state after the longest text, before any weight is
    # rounded
    longest = max(texts, key=len)
    state_want = reference.first_layer_state(
        params, longest, builder["model_args"]) if hasattr(
            runner, "state_error") else None
    verdicts = {}
    # the state's control first: float8 rounds the weights where they lie
    for control in sorted(getattr(runner, "CONTROLS", ("float8",)),
                          reverse=True):
        shape = builder["model_args"]
        if control == "state_bf16":
            shape = dict(shape, state_dtype="bfloat16")
        else:
            _round_matrices_in_place(params, jnp.float8_e4m3fn)
        requests = []
        for text, (logits, tie) in zip(texts, full):
            served = np.asarray(reference.next_token_logits(
                params, text, shape, n_new)).argmax(-1)
            short = logits.max(-1) - logits[np.arange(n_new), served]
            requests.append({"what": f"{control} reference, {len(text)} "
                                     f"tokens",
                             "short": short.astype(float).tolist(),
                             "router_margin": tie.astype(float).tolist()})
            print(f"  {requests[-1]}", flush=True)
        # what the runner holds beside the tokens: True for a cache a
        # reference has not; a state it does have is held to the
        # runner's own limit (`state_error`)
        beside, error = True, None
        if state_want is not None:
            error = runner.state_error(
                state_want,
                reference.first_layer_state(params, longest, shape))
            beside = error <= float(check["state_relative_error"])
            print(f"  precision control {control}: first-layer state "
                  f"{error} from the float32 reference's, relative (at "
                  f"most {check['state_relative_error']})", flush=True)
        ok, worst, lines = runner.verdict(check, requests, beside)
        for line in lines:
            print(f"  precision control {control}: " + line, flush=True)
        verdicts[control] = {"correct": ok, "worst_shortfall": worst,
                             "requests": requests}
        if error is not None:
            verdicts[control]["state_relative_error"] = error
    passed = any(v["correct"] for v in verdicts.values())
    # the float8 control's verdict at the top, as every cell has it; a
    # runner's other controls beside it under their names
    print(json.dumps({"control": "float8_e4m3fn", "seed": args.seed,
                      **verdicts.pop("float8"), **verdicts}))
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
