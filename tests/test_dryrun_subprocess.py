"""Dry-run machinery test in a SUBPROCESS with 8 fake devices — the main
test process must keep its single CPU device (no global XLA_FLAGS)."""
import json
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.launch.mesh import make_mesh, dp_axes
    from repro.launch.dryrun import collective_bytes, lower_cell

    assert jax.device_count() == 8  # dryrun's setdefault kept our count
    mesh = make_mesh(dp=4, tp=2)
    assert mesh.devices.size == 8
    assert dp_axes(mesh) == ("data",)
    out = {}
    for arch, shape in [("internlm2-1.8b", "train_4k"),
                        ("schnet", "molecule"),
                        ("two-tower-retrieval", "retrieval_cand")]:
        with mesh:
            jitted, args = lower_cell(arch, shape, mesh)
            compiled = jitted.lower(*args).compile()
            cost = compiled.cost_analysis()
            coll = collective_bytes(compiled.as_text())
            out[f"{arch}/{shape}"] = {
                "flops": float(cost.get("flops", -1)),
                "n_collectives": sum(v["count"] for v in coll.values())}
    print("RESULT:" + json.dumps(out))
""")


@pytest.mark.slow
def test_dryrun_small_mesh_compiles():
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert len(out) == 3
    lm = out["internlm2-1.8b/train_4k"]
    assert lm["flops"] > 0
    assert lm["n_collectives"] > 0, "sharded train step must communicate"
