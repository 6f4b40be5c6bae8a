"""The accepted token configurations' fused programs are what they were
before the next one joined the decoder (first the five before the sixth; PR
48, which taught the optimizer, the policy and the loss a step that yields a
block of positions, left the six recorded hashes as they stood): `_anakin_fn` of each cell at its
rehearsal size lowers to the text it lowered to at the parent of PR 45 (the
texts' hashes were recorded there, from a `git archive` of that commit), so
that what `dropless_experts`, `causal.block`, `decode` and the policy state's
bookkeeping gained for a layer that is one function, an expert without a
gate matrix and a fourth kind of state is shown not to reach them: no
operation added, dropped or moved. A change that is MEANT to alter one of
these programs records the new hash here, in the PR that measures the cell.

PR 57 (a learner's rotation and gate as one pass over a head's rows,
`models/rowwise.py`) is MEANT to change none of the eight: the pass is taken
by the static shape (`rowwise.whole_tiles`: a head of whole lane tiles, a
fragment of whole tiles of 512 positions) and, like every kernel, by the
platform, and no rehearsal's shape has such tiles (heads of 16 to 32,
fragments of 32), so every rehearsal keeps `rope` and the eight hashes
stand, GLM's, Kimi's and Nemotron-H's (whose real cells never reach the
pass) with them. Of the real cells' programs, lowered for a TPU: those
three's and LFM2's are the parent's text (`_scratch`-style, PERF.md section
6, PR 57); OLMoE's, SmallThinker's, SDAR's, Qwen3-Next's and Laguna's
changed, as meant, and were measured there.
"""

import hashlib
import importlib
import json
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# sha256 of `_anakin_fn.lower(..).as_text()` (StableHLO, no locations) at the
# cell's `rehearse_trainer_config`, seed 7, one CPU device.
LOWERED = {
    "olmoe_token_anakin":
        "4fec4e16d174a38996ceabaf8c465abbd230402334e558fa3159482df9536be7",
    "glm47_flash_token_anakin":
        "6b4d8b098ce704eacf91de4658761e154d8eccdbcf55de25658f2155f8c825a3",
    "smallthinker_token_anakin_8k":
        "da33fc9b9a18d8bdbd3f76dd8fa291e8fd6129238a084c576b56263c2ad53149",
    "lfm2_token_anakin_4k":
        "43c36d8bd5802cec6d145ec2e336d051e5c5a4218cc2a0fd5bc451f2bd223ecd",
    "kimi_linear_token_anakin_4k":
        "b074e3a5eb77a14c26456ba836df77090edb2771a82888895a652b3ace05b2f0",
    # The sixth, recorded at the parent of PR 47 (the grouped products'
    # kernel), whose rule takes no rehearsal's shape.
    "nemotron_h_token_anakin_2k":
        "525b2b0cc7d801a41fad7a5b12eda8929be35f728f6a6ade6e66b763e3547eb0",
    # The seventh, the one program whose rollout scan is over blocks: brought
    # by PR 48, recorded anew by PR 49, which was meant to alter it (the
    # block's keys and values reach the attention as operands, and only the
    # commit pass writes) and measured the cell, and by PR 51, likewise (the
    # learner's last layer makes its clean stream's keys and values alone).
    "sdar_block_token_anakin_2k":
        "c0916c3b76722bd443457859164d9128980b84409be2d9b3527f8b935c8c6133",
    # The eighth, brought by PR 52 (the delta rule under one decay a head;
    # the seven above kept their hashes through it).
    "qwen3_next_token_anakin_4k":
        "783f59e85031328c545fbdb2bfab2d60403b8f66cf0d43647d80323c8f90328c",
}


@pytest.mark.parametrize("cell", LOWERED)
def test_an_accepted_token_cell_lowers_to_the_text_it_had(cell):
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           workload["config"] + ".json")) as f:
        config = json.load(f)
    driver = importlib.import_module("drivers." + workload["driver"])
    session = driver.open_session(config, workload, 7, 1, True)
    try:
        opt, policy = session.optimizer, session.policy
        text = opt._anakin_fn.lower(
            policy.params, policy.opt_state, opt._env_state, opt._obs,
            opt._rng, opt._ep_rew, opt._ep_len, opt._pstate).as_text()
    finally:
        session.close()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[cell], (
        f"{cell}'s fused program changed: {len(text.splitlines())} lines")
