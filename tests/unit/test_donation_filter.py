"""The decode-path donation-warning suppression must survive jax
rewording the message around its core phrase (decoding._arm_donation_filter
matches a `re.escape`d fragment, not the whole sentence)."""

import warnings

from cloud_tpu.models import decoding


def _emitted(messages):
    """Arms the filter, emits each message as UserWarning, returns the
    ones that got through."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        decoding._arm_donation_filter()
        for message in messages:
            warnings.warn(message, UserWarning)
    return [str(w.message) for w in caught]


class TestDonationFilter:

    def test_exact_jax_0_4_37_text_suppressed(self):
        assert _emitted([
            "Some donated buffers were not usable: f32[8]{0}."]) == []

    def test_reworded_suffix_still_suppressed(self):
        # A jax upgrade appending/rewriting everything after the core
        # phrase must not re-surface the warning.
        assert _emitted([
            "Some donated buffers were not usable because the layouts "
            "differed (see the new sharding docs)."]) == []

    def test_reworded_prefix_still_suppressed(self):
        # ... and neither must a rewritten lead-in: the filter pattern
        # carries a leading wildcard, so the fragment may sit anywhere.
        assert _emitted([
            "jax: 2 donated buffers were not usable under mesh "
            "sharding."]) == []

    def test_unrelated_userwarning_passes_through(self):
        assert _emitted(["Some donated buffers were great."]) == [
            "Some donated buffers were great."]

    def test_arming_is_idempotent(self):
        with warnings.catch_warnings():
            warnings.resetwarnings()
            decoding._arm_donation_filter()
            before = len(warnings.filters)
            decoding._arm_donation_filter()
            decoding._arm_donation_filter()
            assert len(warnings.filters) == before

    def test_fragment_is_escaped(self):
        # The installed pattern must treat the fragment literally —
        # guard against a future fragment containing regex
        # metacharacters silently widening the suppression.
        import re
        assert re.escape(decoding._DONATION_FRAGMENT) in (
            decoding._DONATION_PATTERN)
