"""C-ABI embeddable worker (native/embed.c -> libcakeembed.so).

The reference ships its embedding surface as a C-ABI cdylib any host can
link (cake-ios/src/lib.rs:9-56 through uniffi); round 2 only had the Python
``cake_tpu.embed`` counterpart. These tests prove the native library from a
REAL non-Python host: a small C program (tests/embed_host.c) links the
.so, starts a worker, and a distributed master generates through it —
token-exact against the local oracle.
"""

import os
import shutil
import site
import subprocess
import sys
import sysconfig
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import (
    LlamaGenerator,
    LocalForwardStep,
    SamplingConfig,
)
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.io.safetensors_io import save_tiny_checkpoint

REPO = Path(__file__).resolve().parents[1]
LIB = REPO / "cake_tpu" / "native" / "libcakeembed.so"
HOST_SRC = Path(__file__).parent / "embed_host.c"

GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)


def _build_artifacts(tmp_path):
    """Compile the cdylib (if stale/missing) and the C host program."""
    cc = shutil.which("gcc") or shutil.which("clang")
    if cc is None:
        pytest.skip("no C compiler")
    from cake_tpu.native.build import build_embed

    if build_embed(verbose=False) is None:
        pytest.skip("libcakeembed.so could not be built here")
    host = tmp_path / "embed_host"
    subprocess.run(
        [cc, "-O2", "-Wall", "-Werror", str(HOST_SRC), "-o", str(host),
         f"-L{LIB.parent}", "-lcakeembed", f"-Wl,-rpath,{LIB.parent}"],
        check=True,
    )
    return host


def _host_env():
    """The embedded interpreter starts from the BASE prefix, not this venv:
    hand it our site-packages + repo on PYTHONPATH, and the CPU JAX env
    (tests never take the chip).
    """
    env = dict(os.environ)
    paths = [str(REPO), *site.getsitepackages()]
    purelib = sysconfig.get_path("purelib")
    if purelib not in paths:
        paths.append(purelib)
    env["PYTHONPATH"] = ":".join(paths)
    env["JAX_PLATFORMS"] = "cpu"
    # The C ABI (like cake-ios) has no dtype parameter; precision comes from
    # env — f32 here so the token oracle is exact vs the f32 local run.
    env["CAKE_EMBED_DTYPE"] = "f32"
    return env


def test_c_host_worker_serves_token_exact(tmp_path):
    """A pure-C host links the cdylib, becomes a worker, and the master's
    stream through it matches the local oracle exactly."""
    import yaml

    from cake_tpu.parallel.topology import Topology
    from cake_tpu.runtime.master import DistributedForwardStep

    host = _build_artifacts(tmp_path)
    cfg = LlamaConfig.tiny(num_hidden_layers=4)
    params = M.init_params(cfg, jax.random.PRNGKey(51), jnp.float32)
    model_dir = tmp_path / "model"
    save_tiny_checkpoint(model_dir, params, cfg)
    topo_dict = {
        "cnode": {"host": "placeholder", "layers": ["model.layers.1-2"]}
    }
    topo_path = tmp_path / "topology.yml"
    topo_path.write_text(yaml.safe_dump(topo_dict))

    def oracle():
        gen = LlamaGenerator(
            cfg,
            LocalForwardStep(cfg, params, max_seq_len=96, cache_dtype=jnp.float32),
            ByteTokenizer(),
            GREEDY,
        )
        gen.add_message(Message.user("c abi host"))
        gen.generate(5)
        return gen.generated_token_ids

    want = oracle()

    proc = subprocess.Popen(
        [str(host), "cnode", str(model_dir), str(topo_path)],
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        env=_host_env(),
        text=True,
    )
    try:
        line = proc.stdout.readline()
        assert line.startswith("READY "), (line, proc.stderr.read())
        port = int(line.split()[1])

        topo = Topology.from_dict(topo_dict)
        topo.nodes["cnode"].host = f"127.0.0.1:{port}"
        step = DistributedForwardStep(
            cfg, model_dir, topo, dtype=jnp.float32, max_seq_len=96
        )
        try:
            gen = LlamaGenerator(cfg, step, ByteTokenizer(), GREEDY)
            gen.add_message(Message.user("c abi host"))
            gen.generate(5)
            got = gen.generated_token_ids
        finally:
            step.close()
    finally:
        try:
            proc.stdin.close()
            rc = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise
    assert rc == 0, proc.stderr.read()
    assert got == want
