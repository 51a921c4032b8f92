"""The control: the plain reference in the engine's place, computed with
the one guarantee of the configuration broken (``Client.control``).

    python3 perfbench/control.py --workload <cell> --seed <n> --seconds <s>

Drives the cell's own traffic through the harness exactly as
``run.py`` does and prints the same result line; the comparison has to
come out not correct.  Its ``wrong_answers`` count is the upper reading
from which the limit is set.  It needs no chip and does not look for
one; the benchmark's own runs never run it.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Answer:
    """A request that the control answers when first asked for it."""

    def __init__(self, engine: "ControlEngine"):
        self.engine = engine
        self.value = None
        self.t_done = None

    def done(self) -> bool:
        return True

    def result(self, timeout=None) -> bytes:
        if self.t_done is None:
            self.engine.answer_pending()
        return self.value


class ControlEngine:
    """Answers each payload with ``client.control`` of the request that
    the client built it for: every request not yet answered in one
    vectorised call, so the reference keeps up with the cell's load."""

    def __init__(self, options, client):
        self.items: dict = {}
        self.pending: list = []
        build = client.payload

        def payload(index: int, size: int) -> bytes:
            p = build(index, size)
            self.items[p] = (index, size)
            return p

        client.payload = payload
        self.client = client

    def submit(self, payload: bytes, *, op: str) -> _Answer:
        answer = _Answer(self)
        self.pending.append((self.items[payload], answer))
        return answer

    def answer_pending(self) -> None:
        pending, self.pending = self.pending, []
        values = self.client.control([item for item, _ in pending])
        now = time.perf_counter()
        for (_, answer), value in zip(pending, values):
            answer.value, answer.t_done = value, now

    def run_once(self) -> int:
        return 0

    def start(self) -> None:
        pass

    def close(self) -> None:
        pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    from perfbench import run
    from perfbench.spec import load_cell

    cell = load_cell(args.workload, root=ROOT)
    result = run.run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=False, require_tpu=False,
                          engine_factory=ControlEngine)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
