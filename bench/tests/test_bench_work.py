"""The roofline and MFU work counts against numbers worked by hand at the
published widths."""
import json
from pathlib import Path

import pytest

from bench.reference import reference_module
from bench.weights import n_values
from bench.workcount import WorkCounter

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def model_bytes(cfg):
    """Bytes of all the weights in bf16."""
    return 2 * n_values(reference_module(cfg).layout(cfg))


def test_phi3_weights_are_7_64_gb():
    # per layer 4 * 3072^2 attention + 3 * 3072 * 8192 FFN + 2 * 3072 norms
    # = 113,252,352; 32 layers, embedding and head 32064 * 3072 each, and
    # the final norm: 3,821,079,552 values, two bytes each in bf16
    assert model_bytes(config("phi3-mini-3.8b")) == 7_642_159_104


def test_minicpm3_weights_are_8_52_gb():
    # per layer: W_dq 2560*768, W_uq 768*40*96, W_dkv 2560*288,
    # W_uk and W_uv 256*40*64 each, W_o 40*64*2560, FFN 3*2560*6400, and
    # norms 768 + 256 + 2*2560 = 62,674,944; 62 layers and 2 * 73448 * 2560
    assert model_bytes(config("minicpm3-4b")) == 8_523_805_696


def test_phi3_decode_call():
    """Four sequences, each with 300 tokens cached, each decode one token."""
    w = WorkCounter(config("phi3-mini-3.8b")).call([(300, 1, 1)] * 4)
    blocks = 2 * 32 * (4 * 3072 * 3072 + 3 * 3072 * 8192)   # 2 * params
    attn = 32 * 2 * 32 * 2 * 96 * 301                       # QK^T and PV
    head = 2 * 32064 * 3072
    assert w.flops == 4 * (blocks + attn + head)
    weights = 7_642_159_104 - 2 * 32064 * 3072              # no embed table
    kv = 2 * 32 * 32 * 96 * 2                               # 393,216 B/token
    assert kv == 393_216
    assert w.bytes == weights + 4 * (3072 * 2 + 301 * kv)
    secs, bound = w.seconds(PEAK)
    assert bound == "bytes"
    assert secs == pytest.approx(w.bytes / 819e9)
    assert secs == pytest.approx(0.01, rel=0.05)     # 10 ms at 819 GB/s


def test_phi3_prefill_chunk_counts_tokens_and_context():
    """A chunk of 8 prompt tokens after 16 cached, emitting nothing, beside
    a decoding sequence: the head counts only for the emitted token."""
    c = config("phi3-mini-3.8b")
    w = WorkCounter(c)
    got = w.call([(16, 8, 0), (40, 1, 1)])
    per_tok = 2 * 32 * (4 * 3072 * 3072 + 3 * 3072 * 8192)
    keys = 8 * 16 + 8 * 9 // 2 + 41
    assert got.flops == 9 * per_tok + 32 * 2 * 32 * 2 * 96 * keys \
        + 2 * 32064 * 3072
    assert got.bytes == w.weight_bytes + 9 * 6144 + (24 + 41) * 393_216


def test_minicpm3_cache_and_absorbed_decode():
    c = config("minicpm3-4b")
    w = WorkCounter(c)
    assert w.cache_bytes_per_token == 62 * (256 + 32) * 2    # 35.7 KB
    got = w.call([(100, 1, 0)])
    per_layer = (2 * (2560 * 768 + 768 * 40 * 96 + 2560 * 288
                      + 2 * 256 * 40 * 64 + 40 * 64 * 2560
                      + 3 * 2560 * 6400)
                 + 2 * 40 * (2 * 256 + 32) * 101)
    assert got.flops == 62 * per_layer


def test_minicpm3_long_chunk_takes_the_expanded_form():
    """For many new tokens, forming K and V once per position is cheaper
    than absorbing W_uk and W_uv per query, and is what counts."""
    c = config("minicpm3-4b")
    from bench.references import mla
    k, p = 2048, 0
    rest = (2560 * 768 + 768 * 40 * 96 + 2560 * 288 + 40 * 64 * 2560
            + 3 * 2560 * 6400)
    up = 2 * 256 * 40 * 64
    keys = k * (k + 1) // 2
    expanded = 2 * rest * k + 2 * up * k + 2 * 40 * 160 * keys
    absorbed = 2 * (rest + up) * k + 2 * 40 * 544 * keys
    assert expanded < absorbed
    assert mla.slot_flops(c, p, k) == 62 * expanded


def test_mfu_of_a_window(tmp_path):
    """MFU reads the window's needed operations over seconds times peak."""
    from types import SimpleNamespace
    import importlib.util
    path = Path(__file__).resolve().parents[1] / "metrics" / "mfu_pct.py"
    spec = importlib.util.spec_from_file_location("mfu_pct", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    c = config("phi3-mini-3.8b")
    steps = [SimpleNamespace(slots=[(300, 1, 1)] * 4)] * 40
    run = SimpleNamespace(window=SimpleNamespace(steps=steps), window_s=1.0,
                          work=WorkCounter(c), peak=PEAK)
    want = 100 * 40 * WorkCounter(c).call([(300, 1, 1)] * 4).flops / 197e12
    assert mod.read(run) == pytest.approx(want)
    assert 0.5 < mod.read(run) < 1.0     # 40 decode steps of 4 in a second


def test_roofline_share_and_its_bound():
    """Four decode steps that each took 25 ms on the device: the share is
    the least time of their work over 0.1 s, bytes-bound; a trace that
    holds another number of steps gives no share."""
    from types import SimpleNamespace
    from bench.harness import Run, Step
    from bench.trace import TraceSummary
    c = config("phi3-mini-3.8b")
    steps = [Step("decode", [(300, 1, 1)] * 4, 4)] * 4
    trace = TraceSummary(1.0, 0.5, 1, step_device_s=[0.025] * 4)
    run = Run(None, SimpleNamespace(steps=steps, trace=trace), 0.0, 4,
              WorkCounter(c), PEAK)
    share, note = run.roofline("decode")
    least = 4 * WorkCounter(c).call([(300, 1, 1)] * 4).seconds(PEAK)[0]
    assert share == pytest.approx(100 * least / 0.1)
    assert 35 < share < 45 and note.startswith("bytes-bound")
    assert run.roofline("prefill") is None
    trace.step_device_s.append(0.025)
    assert run.roofline("decode") is None
