"""Options the platform decides (``repro.core.platform``): Pallas
interpret mode, the serving backend and hardware config, the persistent
compilation cache's place, and the decode step compiled before serving."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import configs
from repro.core import platform
from repro.core.hwconfig import DEVICE_KINDS, REGISTRY
from repro.models.build import build_model
from repro.serving import EngineConfig, Request, SamplingParams, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cpu_defaults_keep_interpret_and_jnp():
    assert not platform.on_tpu()
    assert platform.resolve_interpret(None) is True
    assert platform.resolve_interpret(False) is False
    ec = EngineConfig()
    assert (ec.backend, ec.hw, ec.interpret) == ("jnp", "tpu_v5e", True)
    explicit = EngineConfig(backend="pallas", hw="cpu_test", interpret=False)
    assert (explicit.backend, explicit.hw, explicit.interpret) == \
        ("pallas", "cpu_test", False)


class _Device:
    def __init__(self, kind):
        self.device_kind = kind


def test_device_kind_maps_to_a_registered_config():
    assert platform.hw_name_for_device(_Device("TPU v5 lite")) == "tpu_v5e"
    assert set(DEVICE_KINDS.values()) <= set(REGISTRY)
    with pytest.raises(KeyError, match="TPU v9"):
        platform.hw_name_for_device(_Device("TPU v9"))


@pytest.mark.parametrize("env_dir", [False, True])
def test_compilation_cache_directory(tmp_path, env_dir):
    """Entries land in ``$JAX_COMPILATION_CACHE_DIR`` when it is set, and
    in ``<checkout>/.jax_cache`` otherwise (run in a child: the cache is
    process-wide state)."""
    checkout, elsewhere = tmp_path / "checkout", tmp_path / "elsewhere"
    checkout.mkdir()
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(elsewhere)
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.core.platform import enable_compilation_cache
        print(enable_compilation_cache({str(checkout)!r}))
        jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)))
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    want = elsewhere if env_dir else checkout / ".jax_cache"
    assert out.stdout.strip().splitlines()[-1] == str(want)
    assert want.is_dir() and any(want.iterdir())
    if env_dir:
        assert not (checkout / ".jax_cache").exists()


def test_decode_compile_failure_raises_before_serving():
    """The decode step compiles at warm-up, outside the device-step
    failure handler: a compiler refusal raises instead of turning into
    requeued and failed requests."""
    cfg = configs.get("qwen3-4b").scaled(n_layers=1)
    model = build_model(cfg)
    import jax

    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, EngineConfig(slots=2, max_len=32, page_size=8))

    def refused(*args):
        raise RuntimeError("Mosaic refused the kernel")

    eng._decode_fn = refused
    eng.submit(Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                       sampling=SamplingParams(max_new_tokens=4)))
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        eng.run(params)
    assert not any(e["event"] in ("requeue", "device_step_failed")
                   for e in eng.events())
