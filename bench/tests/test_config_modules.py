"""A configuration names its own reference, and the reference module its
rehearsal size, extra architecture keys and FLOP count, so that another
architecture joins the benchmark by adding files; a configuration that names
none keeps the dense reference and the sizes every cell had."""
import json
import shutil

import numpy as np
import pytest

import harness
import reference as ref
import run

CONFIGS = ["granite-3-2b.exact", "granite-3-2b.approx-mul8x8_2"]


def requests(vocab, seed=3):
    """Three requests of different lengths, tokens in the vocabulary."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, p).astype(np.int32),
             rng.integers(0, vocab, s).astype(np.int64))
            for p, s in ((11, 5), (30, 1), (4, 17))]


@pytest.mark.parametrize("precision", ["reference", "control"])
@pytest.mark.parametrize("name", CONFIGS)
def test_dense_served_logits_are_the_direct_calls(name, precision):
    conf, _ = run.rehearsal(harness.load_config(name), harness.load_traffic("offline"))
    mod = harness.reference_module(conf)
    assert mod.__file__ == str(harness.BENCH / "reference.py")
    key = harness.prng_key(2**33 + 9)
    reqs = requests(conf["vocab_size"])
    got, served = mod.served_logits(conf, key, reqs, 512, 32, precision)

    packed, want_served = ref.pack(reqs, 512, 32)
    keep = want_served >= 0
    want = np.asarray(ref.logits(ref.arch_of(conf), key, packed, precision))[keep]
    assert got.dtype == np.float32 and got.shape == (23, conf["vocab_size"])
    np.testing.assert_array_equal(served, want_served[keep])
    np.testing.assert_array_equal(got, want)


STUB = '''"""A stand-in reference for a new architecture: the dense reference,
with every call recorded, its own rehearsal sizes and one architecture key
of its own."""
import reference as dense

REHEARSAL = {"num_hidden_layers": 3, "hidden_size": 128,
             "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
             "intermediate_size": 192, "vocab_size": 384,
             "attention_q_chunk": 32, "as_run": {"padded_vocab_size": 512}}
ARCH_KEYS = {"attention_q_chunk": "q_chunk"}
CALLS = []


def served_logits(conf, key, requests, T, S, precision):
    CALLS.append({"conf": conf, "requests": len(requests), "precision": precision})
    return dense.served_logits(conf, key, requests, T, S, precision)


def model_flops(conf, start, stop):
    return dense.model_flops(conf, start, stop)
'''


def stub_root(tmp_path, stub=STUB):
    """A copy of the benchmark with the stub reference and a configuration
    and cell that name it, beside the existing files."""
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(harness.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    root = tmp_path / "bench"
    (root / "references").mkdir()
    (root / "references" / "stub.py").write_text(stub)
    conf = harness.load_config("granite-3-2b.exact", root)
    conf.update(name="stub-dense", reference="stub", attention_q_chunk=512)
    (root / "configs" / "stub-dense.json").write_text(json.dumps(conf))
    cell = harness.load_cell("granite-3-2b.exact.offline", root)
    cell.update(name="stub-dense.offline", config="stub-dense")
    (root / "cells" / "stub-dense.offline.json").write_text(json.dumps(cell))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["workloads"].append({k: cell[k] for k in
                               ("name", "config", "traffic", "chips", "why")})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, conf


def test_new_architecture_joins_by_adding_files(tmp_path):
    root, conf = stub_root(tmp_path)
    r = run.run("stub-dense.offline", 2**34 + 21, 2.0, False, rehearse=True, root=root)
    assert r["correct"], r["checks"]
    stub = harness.reference_module(conf, root)
    assert stub.__file__ == str(root / "references" / "stub.py")
    assert [c["precision"] for c in stub.CALLS] == ["reference"]
    seen = stub.CALLS[0]["conf"]
    assert (seen["num_hidden_layers"], seen["num_key_value_heads"],
            seen["intermediate_size"], seen["vocab_size"],
            seen["attention_q_chunk"]) == (3, 2, 192, 384, 32)
    assert seen["program_overrides"]["q_chunk"] == 32
    assert seen["program_overrides"]["d_ff"] == 192
    assert r["checks"]["reference_tokens"]["value"] >= 1


def test_rehearsal_key_the_program_would_not_get_is_refused(tmp_path):
    stub = STUB.replace('"attention_q_chunk": 32,', '"attention_q_chunk": 32, "ssm_state": 16,')
    root, conf = stub_root(tmp_path, stub)
    with pytest.raises(ValueError, match=r"stub-dense: the rehearsal keys \['ssm_state'\] "
                                         r"map to no program attribute"):
        run.rehearsal(conf, harness.load_traffic("offline", root), root)


@pytest.mark.parametrize("key,value,theirs", [
    ("hidden_size", 1024, "d_model=2048"),
    ("attention_q_chunk", 256, "q_chunk=512"),
])
def test_mismatched_arch_key_is_refused(tmp_path, key, value, theirs):
    prog = harness.import_program()
    root, conf = stub_root(tmp_path)
    harness.model_config(prog, conf, root)
    conf[key] = value
    with pytest.raises(ValueError, match=f"granite-3-2b: the program has {theirs}, "
                                         f"the configuration file {key}={value}"):
        harness.model_config(prog, conf, root)
