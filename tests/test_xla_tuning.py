"""Overlap-flag helper tests (tier-1-safe, no backend init): the merge
must be idempotent, must never clobber user-set entries, and must put
the TPU compiler's flags where libtpu reads them — never into XLA_FLAGS,
where jaxlib aborts on them."""

from horovod_tpu.common import xla_tuning


def test_merge_appends_only_missing_flags():
    existing = "--xla_tpu_some_user_choice=8"
    merged = xla_tuning.merge_flags(existing,
                                    xla_tuning.TPU_OVERLAP_FLAGS)
    toks = merged.split()
    # User token survives, in place, first.
    assert toks[0] == existing
    for name, value in xla_tuning.TPU_OVERLAP_FLAGS:
        assert f"{name}={value}" in toks


def test_merge_preserves_user_value_for_same_flag():
    user = "--xla_tpu_enable_latency_hiding_scheduler=false"
    merged = xla_tuning.merge_flags(user, xla_tuning.TPU_OVERLAP_FLAGS)
    toks = merged.split()
    assert user in toks
    # The helper's value for that flag must NOT appear alongside.
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" not in toks
    assert sum(t.startswith("--xla_tpu_enable_latency_hiding_scheduler")
               for t in toks) == 1


def test_enable_is_idempotent():
    env = {"LIBTPU_INIT_ARGS": "--xla_foo=bar"}
    first = xla_tuning.enable_overlap_scheduling(env)
    second = xla_tuning.enable_overlap_scheduling(env)
    assert first == second == env["LIBTPU_INIT_ARGS"]
    assert env["LIBTPU_INIT_ARGS"].split().count("--xla_foo=bar") == 1
    assert xla_tuning.overlap_flags_active(env)


def test_enable_never_touches_xla_flags():
    """jaxlib aborts the process on an XLA_FLAGS name it does not know
    — on every backend, the CPU included — and it knows none of these:
    they go to libtpu's own variable, which nothing else reads."""
    existing = "--xla_force_host_platform_device_count=8"
    for env in ({"XLA_FLAGS": existing, "JAX_PLATFORMS": "cpu"},
                {"XLA_FLAGS": existing, "JAX_PLATFORMS": "tpu"},
                {"XLA_FLAGS": existing}):
        out = xla_tuning.enable_overlap_scheduling(env)
        assert env["XLA_FLAGS"] == existing
        assert env["LIBTPU_INIT_ARGS"] == out
        assert xla_tuning.overlap_flags_active(env)


def test_overlap_flags_inactive_until_enabled():
    assert not xla_tuning.overlap_flags_active({})
    # The old home of the flags no longer counts.
    assert not xla_tuning.overlap_flags_active({"XLA_FLAGS": " ".join(
        f"{n}={v}" for n, v in xla_tuning.TPU_OVERLAP_FLAGS)})


def test_extra_flags_and_bare_flag_names():
    env = {"LIBTPU_INIT_ARGS": "--xla_dump_to"}  # bare flag, no value
    out = xla_tuning.enable_overlap_scheduling(
        env, extra_flags=(("--xla_custom_knob", "7"),))
    assert "--xla_custom_knob=7" in out.split()
    assert "--xla_dump_to" in out.split()


def test_config_knob_parses_env(monkeypatch):
    from horovod_tpu.common.config import Config

    monkeypatch.delenv("HVD_TPU_OVERLAP_XLA_FLAGS", raising=False)
    monkeypatch.delenv("HOROVOD_OVERLAP_XLA_FLAGS", raising=False)
    assert Config.from_env().overlap_xla_flags is False
    monkeypatch.setenv("HVD_TPU_OVERLAP_XLA_FLAGS", "1")
    assert Config.from_env().overlap_xla_flags is True
