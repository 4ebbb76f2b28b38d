"""The timed path broken underneath, for the benchmark's own tests: a run so
broken has to come out `correct: false`. Never applied by a cell's run; run.py
passes `--sabotage` (hidden) only when a test asks.

  price  one event's price altered where the match feed builds it
  seq    one match frame dropped before it reaches the match queue (its seqs
         never arrive)
"""

from __future__ import annotations


def apply(kind: str, svc) -> None:
    if kind == "price":
        from gome_tpu.service import matchfeed

        inner = matchfeed.match_result_to_pb
        seen = [0]

        def altered(mr):
            ev = inner(mr)
            seen[0] += 1
            if seen[0] == 100:
                ev.match_node.price += 1.0
            return ev

        matchfeed.match_result_to_pb = altered
    elif kind == "seq":
        queue = svc.bus.match_queue
        inner_publish = queue.publish
        calls = [0]

        def publish(body, *args, **kwargs):
            calls[0] += 1
            if calls[0] == 3:
                return queue.end_offset()
            return inner_publish(body, *args, **kwargs)

        queue.publish = publish
    else:
        raise ValueError(f"unknown sabotage {kind!r}")
