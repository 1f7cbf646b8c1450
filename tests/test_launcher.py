"""Launcher argument validation: every unsupported flag pair must die
fast with a one-line error naming both flags — before any model or mesh
work starts.

Regression context: these combinations used to be rejected (or worse,
silently mis-served) deep inside engine construction, after demo weights
were already built; a couple reached the engine as latent misconfigs.
``validate_args`` now front-loads them all.
"""
import pytest

from repro.launch.serve import build_parser, validate_args


def _args(*argv):
    return build_parser().parse_args(list(argv))


def _expect_exit(match, *argv):
    with pytest.raises(SystemExit, match=match):
        validate_args(_args(*argv))


# -- basic sanity -------------------------------------------------------------

def test_defaults_validate_cleanly():
    validate_args(_args())


def test_requests_must_be_positive():
    _expect_exit("--requests", "--requests", "0")


def test_shared_prompt_must_leave_suffix_room():
    _expect_exit("--shared-prompt", "--prompt-len", "8",
                 "--shared-prompt", "7")


# -- speculative-decode pairs -------------------------------------------------

def test_spec_k_rejects_mesh():
    _expect_exit("--spec-k and --mesh", "--spec-k", "2", "--mesh", "2")


def test_spec_k_rejects_share_prefix_on():
    _expect_exit("--spec-k and --share-prefix", "--spec-k", "2",
                 "--share-prefix", "on")


@pytest.mark.parametrize("family", ["mamba", "xlstm", "hybrid"])
def test_spec_k_rejects_recurrent_families(family):
    _expect_exit(f"--spec-k and --family {family}", "--spec-k", "2",
                 "--family", family)


def test_spec_k_rejects_paged_off():
    _expect_exit("--spec-k and --paged off", "--spec-k", "2",
                 "--paged", "off")


def test_spec_k_valid_combo_passes():
    validate_args(_args("--spec-k", "2", "--family", "transformer"))


# -- int8 KV quantization pairs ----------------------------------------------

def test_int8_rejects_paged_off():
    _expect_exit("--kv-dtype int8 and --paged off",
                 "--kv-dtype", "int8", "--paged", "off")


def test_int8_rejects_spec_k():
    _expect_exit("--kv-dtype int8 and --spec-k",
                 "--kv-dtype", "int8", "--spec-k", "2",
                 "--family", "transformer")


def test_int8_rejects_mesh():
    _expect_exit("--kv-dtype int8 and --mesh",
                 "--kv-dtype", "int8", "--mesh", "2")


@pytest.mark.parametrize("kv_dtype", ["f32", "bf16", "int8"])
def test_kv_dtype_choices_validate_standalone(kv_dtype):
    validate_args(_args("--kv-dtype", kv_dtype))


def test_kv_dtype_rejects_unknown_choice():
    with pytest.raises(SystemExit):
        _args("--kv-dtype", "fp4")


# -- exit status --------------------------------------------------------------

@pytest.mark.parametrize("status,code", [("ok", 0), ("error", 1)])
def test_main_exit_code_follows_request_statuses(monkeypatch, status, code):
    """A smoke run whose requests do not all end ok must exit non-zero:
    a chip failure the engine absorbs into failed requests cannot pass
    as a served run."""
    import numpy as np

    from repro.launch import serve
    from repro.serving import ServeEngine
    from repro.serving.engine import GenerationResult

    def fake_serve(self, prompts):
        return [GenerationResult(request_id=i, prompt=p,
                                 tokens=np.zeros(2, np.int32), latency_s=0.0,
                                 status=status)
                for i, p in enumerate(prompts)]
    monkeypatch.setattr(ServeEngine, "serve", fake_serve)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    assert serve.main(["--family", "transformer", "--smoke", "--direct",
                       "--requests", "2", "--batch", "2",
                       "--max-new", "2"]) == code
