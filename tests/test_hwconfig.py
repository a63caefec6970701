"""HardwareConfig accessor, fingerprint, and mutation-helper contracts.

The design-space sweeps rest on two fingerprint invariants: equal
compilation behavior => equal fingerprint (names excluded, so renamed
sweep points dedupe into one compilation-cache entry), and any
compilation-relevant field change => different fingerprint (no
collisions across distinct configs).
"""
import dataclasses

import pytest

from repro.core import CompilationCache, compile_cached, single_op_program
from repro.core.hwconfig import REGISTRY, HardwareConfig, get_config


def _mm():
    return single_op_program(
        "O[i, j] += A[i, c] * B[c, j]",
        {"A": ((64, 32), "float32"), "B": ((32, 48), "float32"),
         "O": ((64, 48), "float32")},
        out="O",
    )


# --------------------------------------------------------------------------
# get_config accessor
# --------------------------------------------------------------------------
def test_get_config_returns_registry_entries():
    for name in REGISTRY:
        assert get_config(name) is REGISTRY[name]


def test_get_config_unknown_lists_available():
    with pytest.raises(KeyError) as ei:
        get_config("tpu_v9000")
    msg = str(ei.value)
    assert "tpu_v9000" in msg
    for name in REGISTRY:
        assert name in msg


def test_mem_keyerror_names_config_and_units():
    hw = get_config("tpu_v5e")
    with pytest.raises(KeyError) as ei:
        hw.mem("L3")
    msg = str(ei.value)
    assert "L3" in msg and "tpu_v5e" in msg
    for unit in ("HBM", "VMEM", "VREG"):
        assert unit in msg


# --------------------------------------------------------------------------
# fingerprint: changes iff a compilation-relevant field changes
# --------------------------------------------------------------------------
def test_fingerprint_ignores_name():
    hw = get_config("tpu_v5e")
    assert hw.renamed("anything_else").fingerprint() == hw.fingerprint()


def test_fingerprint_is_stable_and_distinct_across_configs():
    fps = {name: get_config(name).fingerprint() for name in REGISTRY}
    assert len(set(fps.values())) == len(fps)
    for name in REGISTRY:
        assert get_config(name).fingerprint() == fps[name]


@pytest.mark.parametrize("mutate", [
    lambda hw: hw.with_mem("VMEM", size_bytes=32 * 2**20),
    lambda hw: hw.with_mem("HBM", bandwidth=1.2e12),
    lambda hw: hw.with_mem("HBM", cache_line_elems=64),
    lambda hw: hw.with_stencil("mxu", dims=(256, 256, 128)),
    lambda hw: hw.with_stencil("mxu", flops=400e12),
    lambda hw: dataclasses.replace(hw, peak_flops=400e12),
    lambda hw: dataclasses.replace(hw, ici_link_bw=100e9),
    lambda hw: hw.with_params(**{"autotile.mem_cap_frac": 0.6}),
    lambda hw: hw.with_params(**{"fuse.prefer": "prologue"}),
    lambda hw: hw.without_pass("fuse"),
])
def test_fingerprint_changes_on_compilation_relevant_field(mutate):
    hw = get_config("tpu_v5e")
    assert mutate(hw).fingerprint() != hw.fingerprint()


def test_fingerprint_param_key_order_insensitive():
    hw = get_config("cpu_test")
    a = hw.with_params(**{"autotile.mem_cap_elems": 1024, "autotile.search": "divisors"})
    b = hw.with_params(**{"autotile.search": "divisors", "autotile.mem_cap_elems": 1024})
    assert a.fingerprint() == b.fingerprint()


def test_setting_param_to_its_current_value_keeps_fingerprint():
    hw = get_config("tpu_v5e")
    same = hw.with_params(**{"autotile.mem_cap_frac": 0.45,
                             "fuse.prefer": "epilogue"})
    assert same.fingerprint() == hw.fingerprint()


# --------------------------------------------------------------------------
# with_params / structural mutators
# --------------------------------------------------------------------------
def _params_of(hw: HardwareConfig, pass_name: str):
    return dict(hw.passes)[pass_name]


def test_with_params_overrides_only_the_named_pass():
    hw = get_config("tpu_v5e")
    tweaked = hw.with_params(**{"autotile.mem_cap_frac": 0.7})
    assert _params_of(tweaked, "autotile")["mem_cap_frac"] == 0.7
    assert _params_of(tweaked, "fuse") == _params_of(hw, "fuse")
    assert _params_of(tweaked, "schedule") == _params_of(hw, "schedule")
    # the original is untouched (configs are frozen values)
    assert _params_of(hw, "autotile")["mem_cap_frac"] == 0.45


def test_with_params_for_absent_pass_is_a_noop():
    hw = get_config("tpu_v5e").without_pass("fuse")
    assert hw.with_params(**{"fuse.prefer": "prologue"}).fingerprint() == hw.fingerprint()


def test_with_mem_replaces_one_unit_and_rejects_unknown():
    hw = get_config("tpu_v5e")
    grown = hw.with_mem("VMEM", size_bytes=256 * 2**20)
    assert grown.mem("VMEM").size_bytes == 256 * 2**20
    assert grown.mem("HBM") == hw.mem("HBM")
    with pytest.raises(KeyError):
        hw.with_mem("L9", size_bytes=1)
    with pytest.raises(KeyError):
        hw.with_stencil("tensorcore", flops=1.0)


# --------------------------------------------------------------------------
# cache sharing: identical fingerprints share one entry, distinct don't
# --------------------------------------------------------------------------
def test_identical_fingerprints_share_one_cache_entry(tmp_path):
    cache = CompilationCache(disk_dir=tmp_path)
    hw = get_config("cpu_test")
    twin = hw.renamed("cpu_test_sweep_point_7")
    assert twin.fingerprint() == hw.fingerprint()
    _, rec1 = compile_cached(_mm(), hw, cache=cache)
    _, rec2 = compile_cached(_mm(), twin, cache=cache)
    assert not rec1.cache_hit and rec2.cache_hit
    assert rec1.key == rec2.key
    assert len(cache) == 1
    # the hit record is still scorable: tilings/trace travel with the
    # memory entry
    assert rec2.tilings == rec1.tilings
    assert rec2.pass_trace and rec2.n_kernels == rec1.n_kernels


def test_memory_hit_record_scorable_without_disk_tier():
    from repro.core.cost import score_pass_trace

    cache = CompilationCache(use_disk=False)
    hw = get_config("cpu_test")
    _, cold = compile_cached(_mm(), hw, cache=cache)
    _, hot = compile_cached(_mm(), hw, cache=cache)
    assert hot.cache_hit and not hot.disk_hit
    cold_score = score_pass_trace(cold.pass_trace, cold.n_kernels)
    hot_score = score_pass_trace(hot.pass_trace, hot.n_kernels)
    assert cold_score.latency_s > 0
    assert hot_score.latency_s == cold_score.latency_s


def test_distinct_configs_do_not_collide(tmp_path):
    cache = CompilationCache(disk_dir=tmp_path)
    hw = get_config("cpu_test")
    other = hw.with_mem("L2", size_bytes=2 << 20).renamed("cpu_test")  # same NAME
    assert other.fingerprint() != hw.fingerprint()
    _, rec1 = compile_cached(_mm(), hw, cache=cache)
    _, rec2 = compile_cached(_mm(), other, cache=cache)
    assert not rec1.cache_hit and not rec2.cache_hit
    assert rec1.key != rec2.key
    assert len(cache) == 2
