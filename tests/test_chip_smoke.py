"""chip_smoke.py off the chip: it refuses to report success without a TPU
or past a failing phase, and its phase functions pass at a tiny size on
the CPU (Pallas kernels in interpret mode) and on 4 virtual devices — the
rehearsal that precedes every chip run."""
import functools
import os
import subprocess
import sys

import pytest

import jax

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
import chip_smoke  # noqa: E402

import paddle_tpu as fluid  # noqa: E402

TINY = dict(chip_smoke.FULL, vocab=256, n_layer=2, n_head=2, d_model=256,
            d_inner=512, seq=256, batch=4, slots=4, prompt_lo=8,
            prompt_hi=40, new_tokens=6, interpret=True, require_tpu=False)


@pytest.fixture(autouse=True)
def _outputs_in_tmp(monkeypatch, tmp_path):
    monkeypatch.setattr(chip_smoke, "_HERE", str(tmp_path))
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))


def test_script_refuses_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "not a TPU" in res.stderr


def test_a_raising_phase_ends_the_run(monkeypatch, capsys):
    """With a TPU pretended and one phase made to raise, main() neither
    returns 0 nor prints the verdict line: the exception leaves the
    script, which exits non-zero."""
    class FakeTpu:
        platform, device_kind = "tpu", "pretend"

    def boom(cfg, place):
        raise RuntimeError("train phase died")

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    monkeypatch.setattr(chip_smoke, "phase_kernels", lambda cfg: None)
    monkeypatch.setattr(chip_smoke, "phase_kda", lambda cfg: None)
    monkeypatch.setattr(chip_smoke, "phase_train", boom)
    with pytest.raises(RuntimeError, match="train phase died"):
        chip_smoke.main([])
    assert '"ok": true' not in capsys.readouterr().out


def test_kernels_phase_tiny(capsys):
    chip_smoke.phase_kernels(TINY)
    assert '"phase": "kernels"' in capsys.readouterr().out


def test_kda_phase_tiny(capsys):
    """The chunked scan and the step against each other, at a tiny head
    and texts that end inside a chunk."""
    tiny = dict(chip_smoke.KDA, heads=2, head_dim=16, seq=128,
                lengths=[100, 70], steps=4, require_tpu=False)
    chip_smoke.phase_kda(tiny)
    out = capsys.readouterr().out
    assert '"phase": "kda"' in out and '"ok": true' in out


def test_train_phase_tiny(capsys):
    chip_smoke.phase_train(TINY, fluid.CPUPlace())
    assert '"phase": "train"' in capsys.readouterr().out


def test_serve_phase_tiny(capsys):
    chip_smoke.phase_serve(TINY, fluid.CPUPlace())
    out = capsys.readouterr().out
    assert '"second_predictor_traces": 0' in out


def test_mistral4_phase_tiny(capsys):
    """One prefill by the expanded path and eight steps by the absorbed
    one, against the full-forward rollout; the prompt passes YaRN's
    original context, so the query scale turns."""
    tiny = dict(chip_smoke.MISTRAL4, vocab=256, d_model=64, n_head=4,
                q_rank=32, kv_rank=24, nope=8, rope=8, v=16, n_expert=8,
                d_expert=24, held=[0, 4], original=16, seq=64, slots=2,
                prompt=40, require_tpu=False)
    chip_smoke.phase_mistral4(tiny, fluid.CPUPlace())
    out = capsys.readouterr().out
    assert '"phase": "mistral4"' in out and '"latent_row": 32' in out
    assert '"rollout_tokens_agreeing": 8' in out


def test_parallel_phase_on_four_virtual_devices(monkeypatch, capsys):
    """The --chips 4 phase on the CPU mesh, with the attention dispatch
    steered onto the Pallas kernels (interpret mode) so the shard_map
    around them is what runs under the 2x2 mesh — and, bare, on the
    single-device comparison."""
    from paddle_tpu.ops import attention as A

    monkeypatch.setattr(A, "_use_pallas", lambda *a: True)
    monkeypatch.setattr(
        A, "pallas_flash_attention_bthd",
        functools.partial(A.pallas_flash_attention_bthd, interpret=True))
    seen = []
    shard_map = jax.shard_map
    monkeypatch.setattr(jax, "shard_map", lambda f, **kw: (
        seen.append(kw["in_specs"][0]), shard_map(f, **kw))[1])
    chip_smoke.phase_parallel(TINY, fluid.CPUPlace())
    assert '"phase": "parallel"' in capsys.readouterr().out
    # the flash kernels: batch over dp, heads (dim 2 of B,T,H,Dh) over mp
    want = jax.sharding.PartitionSpec("dp", None, "mp", None)
    attn = [s for s in seen if len(s) == 4]
    assert attn and all(s == want for s in attn), seen
    # the fused head: rows over dp (its weight's vocabulary over mp)
    head = [s for s in seen if len(s) == 2]
    assert head and all(
        s == jax.sharding.PartitionSpec("dp", None) for s in head), seen
    assert len(attn) + len(head) == len(seen), seen
