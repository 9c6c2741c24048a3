"""Dry-run machinery smoke test on a small host mesh (subprocess so the
XLA device-count flag doesn't leak into other tests)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config, reduced, InputShape
    from repro.launch.dryrun import build_step, shardings_for
    from repro.launch.hlo_analysis import collective_bytes
    from repro.launch.mesh import make_mesh
    from repro.sharding.partition import use_rules

    mesh = make_mesh((2, 4), ("data", "model"))
    results = {}
    for arch, shape in [("qwen3-0.6b", InputShape("t", 64, 8, "train")),
                        ("olmoe-1b-7b", InputShape("d", 64, 8, "decode")),
                        ("rwkv6-7b", InputShape("p", 64, 8, "prefill"))]:
        cfg = reduced(get_config(arch))
        step, args_sds, kind = build_step(cfg, shape)
        in_sh, out_sh, donate = shardings_for(kind, args_sds, mesh, shape)
        with use_rules(mesh):
            compiled = jax.jit(step, in_shardings=in_sh,
                               out_shardings=out_sh,
                               donate_argnums=donate
                               ).lower(*args_sds).compile()
        coll = collective_bytes(compiled.as_text())
        results[arch] = {
            "flops": compiled.cost_analysis().get("flops", 0.0),
            "coll": coll["_total_bytes"],
        }
    print("RESULT:" + json.dumps(results))
""")


@pytest.mark.slow
def test_small_mesh_dryrun_compiles():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, out.stdout
    results = json.loads(line[0][len("RESULT:"):])
    assert set(results) == {"qwen3-0.6b", "olmoe-1b-7b", "rwkv6-7b"}
    for arch, r in results.items():
        assert r["flops"] > 0
        # a 2x4 sharded train/serve step must communicate something
    assert results["qwen3-0.6b"]["coll"] > 0


def test_hlo_collective_parser_units():
    from repro.launch.hlo_analysis import collective_bytes
    hlo = textwrap.dedent("""\
        HloModule test

        %body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
          %ar = f32[8]{0} all-reduce(%x), replica_groups={}, to_apply=%add
          ROOT %t = (s32[], f32[8]) tuple(%i, %ar)
        }

        ENTRY %main () -> f32[8] {
          %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}
          %ag = f32[64]{0} all-gather(%y), dimensions={0}
          ROOT %out = f32[8] get-tuple-element(%w), index=1
        }
    """)
    res = collective_bytes(hlo)
    assert res["all-reduce"]["bytes"] == 8 * 4 * 12      # looped x12
    assert res["all-gather"]["bytes"] == 64 * 4
