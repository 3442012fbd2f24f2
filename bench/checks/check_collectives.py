"""Self-check of the ``collective_exposed_share`` reader on hand-built
device operations of known extent, in the style of ``check_spans.py``.

The first chip's operations hold a collective that compute covers
whole (overlapped), one that it covers in part, one that runs alone
(exposed), and a ``-start``/``-done`` pair around compute. The reader's
share is worked out a second way on a 1-microsecond grid, and must also
equal the exposed time counted by hand. The opcode of each kind of
instruction text is checked, and a window without collectives reads
None.

    python3 bench/checks/check_collectives.py
"""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parent), str(HERE)]

import run  # noqa: E402
import traces  # noqa: E402
from check_spans import grid  # noqa: E402

READER = run.load_metric("collective_exposed_share")
US = 1000

# (instruction text, start us, end us) on the first chip
FIRST = [
    ("%fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(%p.1), kind=kLoop", 0, 100),
    # overlapped: inside fusion.1
    ("%all-reduce.2 = f32[] all-reduce(%x), replica_groups={}", 20, 60),
    # partly exposed: 90..100 covered, 100..130 alone
    ("%collective-permute.3 = f32[8,51126720]{1,0:T(8,128)} "
     "collective-permute(%y), source_target_pairs={{0,1}}", 90, 130),
    ("%convolution.4 = f32[8,8]{1,0} convolution(%a, %b)", 130, 200),
    # exposed: alone
    ("%all-gather-start.5 = (f32[2,64]{1,0}, f32[8,64]{1,0}) "
     "all-gather-start(%z), dimensions={0}", 200, 210),
    ("%fusion.6 = f32[8]{0} fusion(%all-gather-done.7, %w), kind=kLoop",
     210, 250),
    ("%all-gather-done.7 = f32[8,64]{1,0} all-gather-done(%ags)", 250, 280),
    # named after its collective, wrapped in a fusion
    ("%reduce-scatter.8 = f32[2,64]{1,0} fusion(%q), kind=kOutput", 300,
     320),
]
EXPOSED_US = 30 + 10 + 30 + 20
# a second chip, which the reader does not read
OTHER = [("%all-to-all.9 = f32[8]{0} all-to-all(%r)", 0, 400)]


def events(rows) -> list:
    return [traces.Event(name, s * US, e * US) for name, s, e in rows]


def trace_run(first, lo=0, hi=400 * US) -> "traces.TraceRun":
    ops = {"/device:TPU:0": events(first), "/device:TPU:1": events(OTHER)}
    return traces.TraceRun(cell=None, device={}, records=[], plans=[],
                           eval_rows=0, window_s=(hi - lo) / 1e9, busy_s=0.0,
                           compiles=0, ops=ops, lo=lo, hi=hi)


def check_opcodes() -> None:
    want = {"%fusion.1": "fusion", "%all-reduce.2": "all-reduce",
            "%collective-permute.3": "collective-permute",
            "%convolution.4": "convolution",
            "%all-gather-start.5": "all-gather-start",
            "%fusion.6": "fusion", "%all-gather-done.7": "all-gather-done",
            "%reduce-scatter.8": "fusion"}
    for name, _, _ in FIRST:
        got = READER.opcode(name)
        assert got == want[name.split(" = ")[0]], (name, got)
    picked = [n.split(" = ")[0] for n, _, _ in FIRST
              if READER.is_collective(n)]
    assert picked == ["%all-reduce.2", "%collective-permute.3",
                      "%all-gather-start.5", "%all-gather-done.7",
                      "%reduce-scatter.8"], picked
    print(f"ok: opcodes read; collectives picked {picked}")


def check_share() -> None:
    run_ = trace_run(FIRST)
    share = READER.read(run_)
    first = run_.ops["/device:TPU:0"]
    coll = [e for e in first if READER.is_collective(e.name)]
    rest = [e for e in first if not READER.is_collective(e.name)]
    hand = grid(coll, run_.lo, run_.hi) & ~grid(rest, run_.lo, run_.hi)
    hand_share = 100.0 * hand.sum() * US / (run_.hi - run_.lo)
    assert READER.exposed_ns(first) == EXPOSED_US * US, \
        READER.exposed_ns(first)
    assert abs(share - 100.0 * EXPOSED_US / 400) < 1e-12, share
    assert abs(share - hand_share) <= 100.0 * 2 * US * len(first) / 400e3, \
        (share, hand_share)
    assert READER.read(trace_run(FIRST[:1] + FIRST[3:4])) is None
    print(f"ok: collective_exposed_share {share:.3f} % (grid "
          f"{hand_share:.3f} %, by hand {100.0 * EXPOSED_US / 400:.3f} %); "
          "None without collectives")


def main() -> int:
    check_opcodes()
    check_share()
    return 0


if __name__ == "__main__":
    sys.exit(main())
