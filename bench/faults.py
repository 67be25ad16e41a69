"""The control and the planted faults that ``correct`` must catch.

Each is a wrapper ``fault(engine, blocks=..., cfg=...) -> engine`` that
``run.main(fault=...)`` puts between the scheduler and the program, so the
rest of the run (traffic, scheduler, comparison) is the real one.

  control        the exact reference one precision step below the rows the
                 configuration serves (``reference.Control``), in the
                 program's place.
  answer_altered the first returned id of every batch replaced by another
                 row's id.
  half_batch     every odd row of a batch answered with the row before it:
                 half of the batch left out.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import reference
from engine import Engine


def control(_, *, blocks, cfg):
    ref = reference.Control(blocks, cfg["dtype"])

    def step(batch):
        d, i = ref.answers(batch, cfg["k"])
        return d.astype(np.float32), i.astype(np.int32)

    return Engine(step=step, batch=cfg["query_batch"])


control.replaces_program = True  # the program is not built at all


def answer_altered(eng, *, blocks, cfg):
    rows = sum(b.shape[0] for b in blocks)

    def step(batch):
        d, i = eng.step(batch)
        i = np.array(i)
        i[0, 0] = (int(i[0, 0]) + rows // 2) % rows
        return d, i

    return dataclasses.replace(eng, step=step)


def half_batch(eng, *, blocks, cfg):
    def step(batch):
        d, i = eng.step(batch)
        return np.repeat(d[0::2], 2, axis=0)[:len(batch)], \
            np.repeat(i[0::2], 2, axis=0)[:len(batch)]

    return dataclasses.replace(eng, step=step)


FAULTS = {"control": control, "answer_altered": answer_altered,
          "half_batch": half_batch}
