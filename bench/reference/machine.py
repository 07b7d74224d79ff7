"""The simulated machine's costs, in cycles, as ``machine.json`` states
them: what every reference loop charges for a lock-table operation, a
message hop, a dependency check, a stored-procedure op."""

from __future__ import annotations

import dataclasses
import json
import os


@dataclasses.dataclass(frozen=True)
class Machine:
    cycles_per_round: int
    lock_op_cycles: int
    coherence_cycles_per_sharer: int
    waitdie_check_cycles: int
    waitfor_maintain_cycles: int
    dreadlocks_spin_cycles: int
    abort_backoff_rounds: int
    msg_hop_cycles: int
    cc_op_cycles: int
    batch_plan_cycles_per_op: int
    dep_check_cycles: int
    exec_op_cycles: int
    txn_fixed_cycles: int
    recon_cycles: int
    partition_lock_cycles: int
    shared_index_penalty_cycles: int

    def rounds(self, cycles):
        """Rounds that ``cycles`` occupy, rounded up (ints or arrays)."""
        return (cycles + self.cycles_per_round - 1) // self.cycles_per_round

    @property
    def msg_hop_rounds(self) -> int:
        return int(self.rounds(self.msg_hop_cycles))

    @property
    def cc_keys_per_round(self) -> int:
        """Key-ops one CC lane processes per round."""
        return max(1, self.cycles_per_round // self.cc_op_cycles)


def load() -> Machine:
    with open(os.path.join(os.path.dirname(__file__), "machine.json")) as f:
        spec = json.load(f)
    return Machine(**{k: v for k, v in spec.items() if k != "source"})


MACHINE = load()
