"""Runs one cell of the benchmark on the CPU at a tiny size, with the
chip check skipped, and prints the result object as its last line.

    python cpu_run.py <cell> <state dir> [fault]

``fault`` breaks the timed path underneath the harness: ``token``
alters every token where the engine's step produces it; ``state`` has
the step return the slots' state unchanged.
"""
import json
import os
import sys
import time

T = time.monotonic()
import benchpaths  # noqa: E402,F401

from benchlib import harness  # noqa: E402

TINY_LM = {"n_layers": 8, "d_model": 128, "vocab_size": 512, "n_slots": 2,
           "check_requests": 3, "resize_program": True}


def break_tokens(system):
    import numpy as np
    stepper = system.tokens_stepper
    step = stepper.step

    def wrong(state):
        state, outs = step(state)
        return state, (np.asarray(outs) + 1) % system.cfg["vocab_size"]

    stepper.step = wrong


def break_state(system):
    stepper = system.tokens_stepper
    step = stepper.step

    def stale(state):
        _, outs = step(state)
        return state, outs

    stepper.step = stale


def main():
    cell, state = sys.argv[1], sys.argv[2]
    fault = sys.argv[3] if len(sys.argv) > 3 else ""
    os.environ["REPRO_AUTOTUNE"] = "0"
    tr = {"prompt_len": 32, "new_tokens": 8, "clients": 4}
    hook = {"token": break_tokens, "state": break_state}.get(fault)
    result = harness.run_cell(cell, 2**31 + 77, 3.0, False, T,
                              require_chip=False, config_overrides=TINY_LM,
                              traffic_overrides=tr, hook=hook,
                              state_dir=state)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
