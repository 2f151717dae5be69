"""Property tests: scheduler quota invariants under arbitrary op sequences,
and the compressed collective on a real multi-device mesh (subprocess)."""
import subprocess
import sys
import textwrap

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.types import Quota
from repro.serving.request import Phase, Request
from repro.serving.scheduler import QuotaScheduler

OPS = st.lists(st.tuples(
    st.sampled_from(["submit", "admit", "shrink", "grow", "finish"]),
    st.integers(1, 30),     # prompt len / quota knob
), max_size=60)


@settings(max_examples=150, deadline=None)
@given(OPS)
def test_scheduler_never_exceeds_quota(ops):
    s = QuotaScheduler(page_size=8)
    s.add_tenant("t", Quota(slots=3, pages=12))
    rid = 0
    now = 0.0
    for op, n in ops:
        now += 1.0
        if op == "submit":
            rid += 1
            s.submit(Request(rid=rid, tenant="t",
                             prompt=list(range(n)), max_new_tokens=4,
                             arrival_t=now))
        elif op == "admit":
            s.admit_waiting("t")
        elif op == "shrink":
            s.set_quota("t", Quota(slots=max(1, n % 4), pages=max(2, n % 16)))
        elif op == "grow":
            s.set_quota("t", Quota(slots=3 + n % 4, pages=12 + n % 16))
        elif op == "finish" and s.active("t"):
            s.finish("t", s.active("t")[0], now)
        tq = s.tenants["t"]
        # invariants: active ≤ slots; pages_used ≤ pages (post-actuation);
        # no request in two places
        assert len(tq.active) <= tq.quota.slots
        assert tq.pages_used(s.page_size) <= tq.quota.pages
        ids_active = [r.req.rid for r in tq.active]
        ids_wait = [r.req.rid for r in tq.waiting]
        assert not (set(ids_active) & set(ids_wait))
        assert len(ids_active) == len(set(ids_active))


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(1, 40), min_size=1, max_size=20))
def test_scheduler_admission_fifo(prompt_lens):
    """Waiting queue admits in FIFO order (head blocks tail)."""
    s = QuotaScheduler(page_size=8)
    s.add_tenant("t", Quota(slots=2, pages=10))
    rs = []
    for i, n in enumerate(prompt_lens):
        rs.append(s.submit(Request(rid=i, tenant="t", prompt=list(range(n)),
                                   max_new_tokens=2, arrival_t=float(i))))
    admitted = s.admit_waiting("t")
    k = len(admitted)
    assert [r.req.rid for r in admitted] == [r.req.rid for r in rs[:k]]


@pytest.mark.slow
def test_compressed_allreduce_multidevice():
    """int8 error-feedback all-reduce ≈ psum on an 8-device host mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel.compression import compressed_allreduce
        mesh = jax.make_mesh((8,), ("data",))
        def f(x):
            return compressed_allreduce(x, "data")
        g = jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"), axis_names={"data"})
        x = jax.random.normal(jax.random.key(0), (8, 1024))
        with mesh:
            out = jax.jit(g)(x.reshape(-1))
        expect = jnp.tile(x.reshape(8, -1).sum(0), 8)
        err = float(jnp.max(jnp.abs(out - expect)))
        scale = float(jnp.max(jnp.abs(expect)))
        assert err < 0.05 * scale + 0.2, (err, scale)
        print("OK", err)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=240,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
