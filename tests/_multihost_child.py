"""Child process for the multi-host integration test (test_multihost.py).

Usage: python _multihost_child.py <coordinator_port> <process_id>

Each of the two processes joins a jax.distributed cluster over a virtual
4-device CPU backend (8 global devices), builds the SAME PipelineRunner over
the global mesh (4 stages x tp 2), and runs lockstep generation through
MultiHostStep: process 0 drives a greedy LlamaGenerator and checks the token
stream against a local single-device oracle; process 1 replays the leader's
steps until STOP. Prints MH_TOKENS_OK on the leader when the oracle matches.

The env (JAX_PLATFORMS=cpu, device count) must be set by the SPAWNING
process: jax reads it when it is first imported.
"""

import sys

from cake_tpu.parallel import multihost

port, pid = sys.argv[1], int(sys.argv[2])
multihost.initialize(f"127.0.0.1:{port}", num_processes=2, process_id=pid)

import jax
import jax.numpy as jnp

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import (
    LlamaGenerator,
    LocalForwardStep,
    SamplingConfig,
)
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.parallel.multihost import MultiHostStep
from cake_tpu.parallel.pipeline import PipelineRunner

assert len(jax.devices()) == 8, jax.devices()
assert len(jax.local_devices()) == 4

cfg = LlamaConfig.tiny(num_hidden_layers=4)
params = M.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)  # deterministic
runner = PipelineRunner(
    cfg,
    params,
    [(0, 1), (1, 2), (2, 3), (3, 4)],
    tp=2,
    max_seq_len=128,
    cache_dtype=jnp.float32,
)
step = MultiHostStep(runner)

GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)

if step.leader:
    gen = LlamaGenerator(cfg, step, ByteTokenizer(), GREEDY)
    gen.add_message(Message.user("multi host pipeline oracle"))
    gen.generate(8)
    got = list(gen.generated_token_ids)

    # Second dialog exercises RESET on the broadcast channel.
    gen.reset()
    gen.add_message(Message.user("second dialog"))
    gen.generate(4)
    second = list(gen.generated_token_ids)
    step.stop()

    # Local single-device oracle (leader-only computation is fine after STOP).
    oracle = LlamaGenerator(
        cfg,
        LocalForwardStep(cfg, params, max_seq_len=128, cache_dtype=jnp.float32),
        ByteTokenizer(),
        GREEDY,
    )
    oracle.add_message(Message.user("multi host pipeline oracle"))
    oracle.generate(8)
    assert got == list(oracle.generated_token_ids), (got, oracle.generated_token_ids)
    oracle.reset()
    oracle.add_message(Message.user("second dialog"))
    oracle.generate(4)
    assert second == list(oracle.generated_token_ids)
    print("MH_TOKENS_OK", flush=True)
else:
    step.follow()
    print("MH_FOLLOWER_DONE", flush=True)
