"""SampleBatch: columnar trajectory data.

Parity: `rllib/policy/sample_batch.py` — a dict of equal-length numpy
columns with concat/rows/shuffle/slice/split-by-episode, plus
`MultiAgentBatch` for policy-keyed batches. Columns are contiguous numpy
arrays so host→device feeding is a single copy per column (TPU-friendly:
the learner converts whole columns, never per-row objects).
"""

from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np

# Canonical column names (same vocabulary as the reference).
OBS = "obs"
NEW_OBS = "new_obs"
ACTIONS = "actions"
REWARDS = "rewards"
DONES = "dones"
INFOS = "infos"
EPS_ID = "eps_id"
AGENT_INDEX = "agent_index"
T = "t"
ACTION_LOGP = "action_logp"
ACTION_DIST_INPUTS = "action_dist_inputs"
VF_PREDS = "vf_preds"
ADVANTAGES = "advantages"
VALUE_TARGETS = "value_targets"
PREV_ACTIONS = "prev_actions"
PREV_REWARDS = "prev_rewards"
UNROLL_ID = "unroll_id"
SEQ_LENS = "seq_lens"
STATE_IN = "state_in"
STATE_OUT = "state_out"
# Per-fragment bootstrap observation, shape [num_fragments, ...] — one
# row per rollout fragment rather than per step (emitted by the packed
# VectorSampler so the learner never ships a full NEW_OBS column).
BOOTSTRAP_OBS = "bootstrap_obs"
# Behavior-policy selection lag in env steps, [num_rows] int32: how
# stale the observation that selected this row's action was (0 for
# synchronous sampling; j for sub-step j of a `sebulba_onchip_steps`
# window). The stored ACTION_DIST_INPUTS/ACTION_LOGP are always the
# distribution that actually selected the action, so V-trace ratios
# stay exact; this column only records the lag for accounting.
POLICY_LAG = "policy_lag"
# The denoising pass at which a row's token was unmasked, [num_rows]
# int32, of a policy that generates a block of positions a step
# (`JaxPolicy.block_step_state`): 0 .. passes - 1, or -1 for a row whose
# token was GIVEN (an episode's first position), which no policy chose and
# no loss weighs.
UNMASK_STEPS = "unmask_steps"
# The observations of OBS where a fused rollout wrote them, shape
# [groups, T, fragments / groups, ...]: one group of fragments a device,
# time-major inside a group, so entry [g, t, b] is OBS's row
# (g * fragments / groups + b) * T + t. A second view of the same rows,
# never a second copy: a loss whose model is row-wise may read this one
# and put the model's narrow outputs in OBS's order, and the packed OBS
# is then never laid out.
OBS_TIME_MAJOR = "obs_time_major"

# Columns whose leading dimension is NOT the per-step row count.
_NON_ROW_COLUMNS = (SEQ_LENS, BOOTSTRAP_OBS, OBS_TIME_MAJOR)


def packed_from_time_major(x):
    """[groups, T, fragments / groups, ...], the order of OBS_TIME_MAJOR,
    -> packed fragments [fragments * T, ...], the order of OBS."""
    return x.swapaxes(1, 2).reshape((-1,) + x.shape[3:])


class SampleBatch(dict):
    """A dict of columns; all columns share leading dimension `count`."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        lens = {k: len(v) for k, v in self.items()
                if k not in _NON_ROW_COLUMNS}
        if lens and len(set(lens.values())) > 1:
            raise ValueError(f"column lengths differ: {lens}")

    @property
    def count(self) -> int:
        for k, v in self.items():
            if k not in _NON_ROW_COLUMNS:
                return len(v)
        return 0

    # -- construction ----------------------------------------------------
    @staticmethod
    def concat_samples(batches: List["SampleBatch"]) -> "SampleBatch":
        if len(batches) == 1:
            return batches[0]
        keys = batches[0].keys()
        out = {}
        for k in keys:
            vals = [b[k] for b in batches]
            if isinstance(vals[0], np.ndarray):
                out[k] = np.concatenate(vals, axis=0)
            else:
                out[k] = [x for v in vals for x in v]
        return SampleBatch(out)

    def concat(self, other: "SampleBatch") -> "SampleBatch":
        return SampleBatch.concat_samples([self, other])

    def copy(self) -> "SampleBatch":
        return SampleBatch({k: (v.copy() if isinstance(v, np.ndarray)
                                else list(v)) for k, v in self.items()})

    # -- access ----------------------------------------------------------
    def rows(self) -> Iterator[dict]:
        for i in range(self.count):
            yield {k: v[i] for k, v in self.items()
                   if k not in _NON_ROW_COLUMNS}

    def columns(self, keys: List[str]) -> List:
        return [self[k] for k in keys]

    def slice(self, start: int, end: int) -> "SampleBatch":
        # Row slicing drops fragment-indexed columns (BOOTSTRAP_OBS):
        # they no longer align once rows are cut.
        return SampleBatch({k: v[start:end] for k, v in self.items()
                            if k not in _NON_ROW_COLUMNS})

    def shuffle(self, rng: np.random.Generator = None) -> "SampleBatch":
        rng = rng or np.random.default_rng()
        perm = rng.permutation(self.count)
        return SampleBatch({
            k: (v[perm] if isinstance(v, np.ndarray)
                else [v[i] for i in perm])
            for k, v in self.items() if k not in _NON_ROW_COLUMNS})

    def split_by_episode(self) -> List["SampleBatch"]:
        if EPS_ID not in self:
            raise ValueError("no eps_id column")
        eps = np.asarray(self[EPS_ID])
        # boundaries where episode id changes
        cuts = [0] + [i for i in range(1, len(eps)) if eps[i] != eps[i - 1]] \
            + [len(eps)]
        return [self.slice(a, b) for a, b in zip(cuts[:-1], cuts[1:])]

    def timeslices(self, k: int) -> List["SampleBatch"]:
        return [self.slice(i, i + k) for i in range(0, self.count, k)]

    def size_bytes(self) -> int:
        return sum(v.nbytes for v in self.values()
                   if isinstance(v, np.ndarray))

    def __repr__(self):
        return f"SampleBatch({self.count}: {list(self.keys())})"


SEQ_MASK = "seq_mask"


def real_count(batch) -> int:
    """Env steps excluding padding rows (recurrent batches carry a
    seq_mask; feedforward batches count every row)."""
    if isinstance(batch, MultiAgentBatch):
        return batch.count
    if SEQ_MASK in batch:
        return int(np.asarray(batch[SEQ_MASK]).sum())
    return batch.count


class MultiAgentBatch:
    """Batches keyed by policy id (parity: `sample_batch.py:230`)."""

    def __init__(self, policy_batches: Dict[str, SampleBatch], count: int):
        self.policy_batches = policy_batches
        self.count = count  # env steps represented

    @staticmethod
    def of(batch) -> "MultiAgentBatch":
        if isinstance(batch, MultiAgentBatch):
            return batch
        return MultiAgentBatch({"default_policy": batch}, batch.count)

    @staticmethod
    def concat_samples(batches: List["MultiAgentBatch"]) -> "MultiAgentBatch":
        out: Dict[str, List[SampleBatch]] = {}
        count = 0
        for mb in batches:
            count += mb.count
            for pid, b in mb.policy_batches.items():
                out.setdefault(pid, []).append(b)
        return MultiAgentBatch(
            {pid: SampleBatch.concat_samples(bs) for pid, bs in out.items()},
            count)

    def size_bytes(self) -> int:
        return sum(b.size_bytes() for b in self.policy_batches.values())

    def __repr__(self):
        return f"MultiAgentBatch({self.count}: {list(self.policy_batches)})"
