"""Hot-path auditor tests (infw.analysis.jaxcheck + the kernel
entrypoint registry).

The full audit of every registered entrypoint runs in `make entry-check`
/ `make static-check`; the tier-1 subset here exercises the registry
contract and each detector on live jaxprs within the CI time budget.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infw.analysis import jaxcheck
from infw.kernels import kernel_entrypoints


def _by_name():
    return {ep.name: ep for ep in kernel_entrypoints()}


def test_registry_covers_the_dispatch_surface():
    names = {ep.name for ep in kernel_entrypoints()}
    # classify, wire decode and the fused walk must all be registered
    # (the ISSUE contract: an unregistered entrypoint is invisible to
    # the static gate)
    assert {"classify/xla-dense", "classify/xla-trie",
            "classify-wire/xla-trie-fused", "wire-decode/delta-fused",
            "classify/pallas-dense", "classify/pallas-walk",
            "classify-wire/xla-ctrie-fused",
            "classify-wire/xla-ctrie-overlay-fused",
            "classify/pallas-cwalk",
            "patch/txn-scatter-dense",
            "patch/ctrie-joined-scatter"} <= names


def test_builders_return_stable_jitted_objects():
    for ep in kernel_entrypoints():
        fn0, _ = ep.build(128)
        fn1, _ = ep.build(128)
        assert fn0 is fn1, ep.name


def test_audit_xla_dense_clean():
    rep, = jaxcheck.audit_all(
        names=["classify/xla-dense"], ladder=(128, 256)
    )
    assert rep.shapes == [128, 256]
    assert rep.n_pallas_calls == 0
    assert [f for f in rep.findings if f.severity != "info"] == []


def test_audit_pallas_dense_vmem_estimate():
    rep, = jaxcheck.audit_all(
        names=["classify/pallas-dense"], ladder=(256,), execute=False
    )
    assert rep.n_pallas_calls >= 1
    assert rep.vmem_bytes > 0
    assert [f for f in rep.findings if f.severity != "info"] == []
    # a 1-byte budget must fail with the offending block specs attached
    rep_bad, = jaxcheck.audit_all(
        names=["classify/pallas-dense"], ladder=(256,), vmem_budget=1,
        execute=False,
    )
    bad = [f for f in rep_bad.findings if f.check == "vmem-budget"]
    assert bad and bad[0].severity == "error" and "block" in bad[0].detail


def test_wide_dtype_detector():
    def leaky(x):
        return x.astype(jnp.float64) * 2.0

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(leaky)(np.ones(4, np.float32))
        findings = jaxcheck.check_wide_dtypes("t", jaxpr)
    assert findings and findings[0].check == "x64-leak"
    assert "float64" in findings[0].message

    clean = jax.make_jaxpr(lambda x: x * 2)(np.ones(4, np.int32))
    assert jaxcheck.check_wide_dtypes("t", clean) == []


def test_host_callback_detector():
    def with_cb(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    jaxpr = jax.make_jaxpr(with_cb)(np.ones(4, np.float32))
    findings = jaxcheck.check_host_callbacks("t", jaxpr)
    assert findings and findings[0].severity == "error"

    clean = jax.make_jaxpr(lambda x: x + 1)(np.ones(4, np.int32))
    assert jaxcheck.check_host_callbacks("t", clean) == []


def test_recompile_lint_counts_cache_growth():
    ep = _by_name()["classify/xla-dense"]
    findings = jaxcheck._recompile_lint(ep, ladder=(128, 256))
    assert [f for f in findings if f.severity == "error"] == []


def test_summarize_and_json_shapes():
    reports = jaxcheck.audit_all(
        names=["classify/xla-dense"], ladder=(128,), execute=False
    )
    s = jaxcheck.summarize(reports)
    assert s["entries"] == 1
    doc = reports[0].to_dict()
    assert doc["entry"] == "classify/xla-dense"
    assert isinstance(doc["findings"], list)


@pytest.mark.slow
def test_full_registry_audit_clean():
    reports = jaxcheck.audit_all(ladder=(256, 1024))
    s = jaxcheck.summarize(reports)
    assert s["error"] == 0 and s["warning"] == 0, [
        f.to_dict() for f in jaxcheck.all_findings(reports)
    ]


def test_transfer_lint_clean_entrypoint():
    """A registered entrypoint with committed device operands runs clean
    under jax.transfer_guard('disallow')."""
    ep = _by_name()["classify/xla-dense"]
    findings = jaxcheck._transfer_lint(ep, ladder=(128,))
    assert findings == [], [f.to_dict() for f in findings]


def test_transfer_lint_catches_host_operand():
    """The deliberately defective entrypoint (host-resident numpy
    operand) must produce an error-severity implicit-transfer finding —
    the injected acceptance of the transfer lint."""
    ep = jaxcheck.transfer_defect_entrypoint()
    findings = jaxcheck._transfer_lint(ep, ladder=(128,))
    assert findings, "implicit transfer not caught"
    assert all(f.check == "implicit-transfer" for f in findings)
    assert all(f.severity == "error" for f in findings)
    # and through the audit_all plumbing the strict audit fails
    reports = jaxcheck.audit_all(
        names=["defect/implicit-transfer"], ladder=(128,),
        include_transfer_defect=True,
    )
    assert jaxcheck.summarize(reports)["error"] >= 1
