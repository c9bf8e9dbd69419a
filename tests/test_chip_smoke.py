"""chip_smoke.py's phases at tiny sizes on the CPU.

The script runs them at full size on a TPU; here the same phase
functions run with small tables and few packets, Pallas in interpret
mode, and the mesh phase on four of the virtual CPU devices — so the
daemon driving, the NodeState conversion, the ring producer and every
comparison with the reference classifier are exercised on each run.
"""
import json

import numpy as np

import chip_smoke
from infw.compiler import compile_tables

# small enough for the CPU, large enough that B's table takes the trie
# path (more than the 4096-entry dense limit) and keeps a deep v6 class
TRIE = dict(n_entries=5000, width=8, n_deep_v6=50)


def test_nodestate_compiles_back_to_the_tables():
    tables = chip_smoke.phase_b_tables(**TRIE)
    doc = chip_smoke.nodestate_doc(tables)
    from infw.spec import IngressNodeFirewallNodeState

    ns = IngressNodeFirewallNodeState.from_dict(json.loads(json.dumps(doc)))
    back = compile_tables(ns.spec.interface_ingress_rules,
                          chip_smoke.registry(), rule_width=TRIE["width"])
    assert back.num_entries == tables.num_entries
    want = {k.masked_identity(): r for k, r in tables.content.items()}
    got = {k.masked_identity(): r for k, r in back.content.items()}
    assert want.keys() == got.keys()
    assert all(np.array_equal(want[k], got[k]) for k in want)


def test_phase_a_dense(tmp_path):
    line = chip_smoke.phase_a(str(tmp_path), n_entries=64, width=8,
                              n_packets=4096, interpret=True, n_files=2)
    assert line["verdict_mismatches"] == 0 and line["path"] == "dense"


def test_phase_b_trie_and_fused_walk(tmp_path):
    line = chip_smoke.phase_b(str(tmp_path), n_packets=4096, n_files=2,
                              **TRIE)
    assert line["pallas"]["pallas-walk"] > 0


def test_phase_c_ring_resident(tmp_path):
    line = chip_smoke.phase_c(str(tmp_path), admissions=8,
                              record_packets=256, **TRIE)
    assert line["compiles_after_warmup"] == 0
    assert line["resident_fallbacks"] == 0


def test_mesh_phase(tmp_path):
    lines = chip_smoke.phase_mesh(str(tmp_path), n_packets=4096,
                                  platform="cpu", n_files=2, **TRIE)
    assert [ln["phase"] for ln in lines] == ["mesh-4x1", "mesh-2x2"]


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    assert '"ok"' not in capsys.readouterr().out
