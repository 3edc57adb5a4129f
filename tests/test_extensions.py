"""Extensions beyond the base deliverables: WCC, data-driven PR, the
roofline HLO parser, and the train launcher (query serving moved to
repro.service)."""

import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest

from repro.core.algorithms import pagerank, pagerank_delta, wcc
from repro.core.direction import Direction, Fixed, GenericSwitch
from repro.graphs import erdos_renyi, kronecker
from repro.roofline.analysis import (V5E, collective_bytes_from_hlo,
                                     kernel_roofline, peaks,
                                     roofline_report)


@pytest.mark.parametrize("policy", [Fixed(Direction.PUSH),
                                    Fixed(Direction.PULL),
                                    GenericSwitch()])
def test_wcc_matches_networkx(policy, nx_of):
    g = erdos_renyi(250, 1.5, seed=9, weighted=True)
    G = nx_of(g)
    r = wcc(g, policy)
    assert int(r.num_components) == nx.number_connected_components(G)
    # labels constant within each nx component
    labels = np.asarray(r.labels)
    for comp in nx.connected_components(G):
        comp = list(comp)
        assert len(set(labels[comp].tolist())) == 1


def test_wcc_cost_structure():
    g = erdos_renyi(200, 3.0, seed=2)
    push = wcc(g, Fixed(Direction.PUSH)).cost
    pull = wcc(g, Fixed(Direction.PULL)).cost
    assert int(pull.atomics) == 0
    assert int(push.atomics) > 0


def test_pagerank_delta_converges_to_power_iteration():
    g = kronecker(8, 5, seed=3)
    ref = pagerank(g, 150, direction="pull").ranks
    for d in ("push", "pull"):
        r = pagerank_delta(g, tol=1e-8, direction=d)
        np.testing.assert_allclose(np.asarray(r.ranks), np.asarray(ref),
                                   atol=1e-5)
        assert float(r.max_residual) <= 1e-8


def test_pagerank_delta_is_work_efficient():
    """The paper's §3.8 claim quantified: pushing with a shrinking active
    set does less total work than synchronous sweeps."""
    g = kronecker(9, 6, seed=2)
    dd = pagerank_delta(g, tol=1e-8, direction="push").cost
    sync = pagerank(g, 120, direction="push").cost
    assert int(dd.reads) < int(sync.reads)
    assert int(dd.locks) < int(sync.locks)


# ------------------------------------------------------ roofline parser --
HLO_SNIPPET = """
ENTRY %main {
  %ag = bf16[16,128]{1,0} all-gather(%x), dimensions={1}
  %ar.1 = f32[4,4]{1,0} all-reduce(%y), to_apply=%add
  %rs = (f32[8]{0}, f32[8]{0}) reduce-scatter(%a, %b), dimensions={0}
  %a2a-start = s8[2,2,2]{2,1,0} all-to-all-start(%z)
  %a2a-done = s8[2,2,2]{2,1,0} all-to-all-done(%a2a-start)
  %cp = f32[10]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %dot = f32[16,16]{1,0} dot(%p, %q)
}
"""


def test_collective_parser_counts_and_bytes():
    out = collective_bytes_from_hlo(HLO_SNIPPET)
    by = out["by_kind"]
    assert by["all-gather"] == {"count": 1, "bytes": 16 * 128 * 2}
    assert by["all-reduce"] == {"count": 1, "bytes": 4 * 4 * 4}
    assert by["reduce-scatter"]["bytes"] == 2 * 8 * 4
    assert by["all-to-all"] == {"count": 1, "bytes": 8}  # start only
    assert by["collective-permute"]["bytes"] == 40
    # the plain dot must NOT be counted
    assert out["total_count"] == 5


def test_roofline_report_terms():
    fake = {"cost": {"flops": 197e12, "bytes_accessed": 819e9},
            "collectives": {"total_bytes": 25e9}}
    rf = roofline_report(fake)
    assert abs(rf["compute_s"] - 1.0) < 1e-6
    assert abs(rf["memory_s"] - 1.0) < 1e-6
    assert abs(rf["collective_s"] - 0.5) < 1e-6
    assert rf["dominant"] in ("compute", "memory")


def test_roofline_peaks_refuse_unknown_devices():
    """Peaks are looked up by the device kind JAX reports; a device
    without published peaks is an error, never priced as a v5e."""
    assert peaks(V5E)["hbm_bw"] == 819e9
    for call in (lambda: peaks("cpu"),
                 lambda: kernel_roofline("pull", device_kind="TPU v4",
                                         n=1024, d_ell=8),
                 lambda: roofline_report(
                     {"cost": {"flops": 1.0, "bytes_accessed": 1.0},
                      "collectives": {"total_bytes": 0}},
                     device_kind="cpu")):
        with pytest.raises(ValueError, match="no published peaks"):
            call()


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache
    goes to the checkout's fixed .jax_cache directory."""
    import jax
    from repro.compile_cache import CHECKOUT, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == str(CHECKOUT / ".jax_cache")
        assert (CHECKOUT / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------- launchers ---
def test_train_launcher_smoke(tmp_path):
    from repro.launch.train import main
    assert main(["--arch", "gin-tu", "--steps", "3",
                 "--ckpt-dir", str(tmp_path)]) == 0


def test_train_launcher_lm(tmp_path):
    from repro.launch.train import main
    assert main(["--arch", "llama3.2-1b", "--steps", "2", "--batch", "2",
                 "--seq", "16"]) == 0


def test_serving_owned_by_service_layer():
    """The LM decode serving stack is gone: graph query serving lives in
    repro.service (QueryService); repro.serve / launch.serve no longer
    exist."""
    import importlib
    import pytest
    for gone in ("repro.serve", "repro.launch.serve"):
        with pytest.raises(ModuleNotFoundError):
            importlib.import_module(gone)
    from repro.service import QueryService  # noqa: F401 — the successor
