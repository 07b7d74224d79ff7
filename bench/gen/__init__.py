"""Transaction streams for the benchmark, one generator per kind of stream.

A kind is a module of this package, found by the name a configuration
gives as ``workload.kind`` (as ``bench/reference/plans/`` finds a
protocol's planner). The module holds:

* ``Params``: a frozen dataclass of the kind's fields, built on
  :class:`Common` (the fields of every kind);
* ``generate(p: Params) -> Workload``;
* ``CASES``: field dicts (without ``kind``) at which
  ``bench/test_bench.py`` checks that ``generate`` gives the program's
  own arrays.

The generators are copies of the program's (paper section 4 and
Appendix A), kept with the benchmark so that the traffic a cell runs
cannot change with the program; the harness hands the program only
these arrays. A new kind of stream is a new file here: nothing else in
``bench/`` changes.

Arrays (N = num_txns, K = max lock ops per txn): ``keys`` int32[N, K]
in acquisition order, KEY_SENTINEL padded; ``modes`` int32[N, K], 0 read
and 1 write; ``nkeys`` int32[N]; ``part`` int32[N, K], the partition of
each key; ``exec_ops`` int32[N]; ``ollp`` and ``ollp_miss`` bool[N].
"""

from __future__ import annotations

import dataclasses
import importlib
import pkgutil

from bench.reference.data import Workload


@dataclasses.dataclass(frozen=True)
class Common:
    num_txns: int = 1 << 15
    seed: int = 0
    # Batch-epoch size for batch-planned protocols (dgcc / quecc): how many
    # transactions the planner groups into one dependency-graph / queue
    # batch. Larger epochs amortize planning and widen wavefronts but add
    # batching latency.
    batch_epoch: int = 512


def load(kind: str):
    """The generator module of ``kind``; ValueError if there is none."""
    if not isinstance(kind, str) or not kind.isidentifier():
        raise ValueError(f"unknown workload kind {kind!r}")
    name = f"{__name__}.{kind}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"unknown workload kind {kind!r}") from e


def kinds() -> list[str]:
    """The kinds of stream that have a module here."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__))


def make_workload(fields: dict) -> Workload:
    """The stream that ``fields`` describe: ``kind`` names the generator,
    the rest are its ``Params`` (a field it does not have is a
    TypeError that names it)."""
    fields = dict(fields)
    mod = load(fields.pop("kind", None))
    return mod.generate(mod.Params(**fields))
