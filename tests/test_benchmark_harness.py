"""The benchmark's own tests (``benchmarks/tests/``), as tier-1 runs them.

They guard what the ledger's numbers are computed from: that every
per-layer metric of ``BENCHMARK.json`` has a reader that agrees, the window
arithmetic of the end-to-end rates, the reduction of a recorded v5e trace
into scopes, idle gaps and collectives, the traffic's seeding, the float32
references. Collected here, by name, is every one that writes to no
directory another process shares. The rest are run by hand
(``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``): whatever calls
``train.run``, which empties ``benchmarks/.work``; a traced run, which
writes ``benchmarks/.trace/<cell>``; and the untraced serve rehearsals,
whose paged programs would count against the serving tests' program budgets
in a worker they share (``jax.jit``'s cache is per function, not per test).
"""
import pytest

pytest.register_assert_rewrite(
    "benchmarks.tests.test_afmoe", "benchmarks.tests.test_glm4_moe_lite",
    "benchmarks.tests.test_glm_moe_dsa", "benchmarks.tests.test_kimi_linear",
    "benchmarks.tests.test_minicpm_sala", "benchmarks.tests.test_overlap",
    "benchmarks.tests.test_paged",
    "benchmarks.tests.test_reference", "benchmarks.tests.test_scopes",
    "benchmarks.tests.test_spec", "benchmarks.tests.test_stats",
    "benchmarks.tests.test_trace", "benchmarks.tests.test_traffic")

# The five rehearsals of the tiny ``glm-4.7-flash`` cell take a sixth of the
# whole run between them (``ROADMAP.md`` D28) and share nothing but the
# ``compared`` fixture of two of them. A module's cases are collected in the
# order of its names and dealt to the workers in runs of consecutive cases:
# the two controls stand here, the other three at the end of the file, so
# that they are two workers' and not one's.
from benchmarks.tests.test_glm4_moe_lite import (  # noqa: E402,F401
    test_control_is_not_correct as test_glm_lite_control_is_not_correct,
    test_readers_find_nothing_where_nothing_is_theirs,
    test_readers_know_the_operations_a_step_has_to_do,
    test_readers_read_a_made_up_trace_of_the_real_cell,
    test_real_configuration_is_the_catalogs_but_for_what_reduced_names
    as test_glm_lite_real_configuration_is_the_catalogs_but_for_what_reduced_names,
    test_the_mix_is_the_issues_parameter_for_parameter
    as test_glm_lite_the_mix_is_the_issues_parameter_for_parameter,
    test_tiny_cell_lists_what_the_real_cell_lists
    as test_glm_lite_tiny_cell_lists_what_the_real_cell_lists,
    work_dir,
)
from benchmarks.tests.test_afmoe import (  # noqa: E402,F401
    test_readers_know_the_bytes_a_step_has_to_read,
    test_real_configuration_is_the_catalogs_but_for_what_reduced_names
    as test_afmoe_real_configuration_is_the_catalogs_but_for_what_reduced_names,
    test_the_mix_is_the_issues_parameter_for_parameter
    as test_afmoe_the_mix_is_the_issues_parameter_for_parameter,
    test_tiny_cell_lists_what_the_real_cell_lists
    as test_afmoe_tiny_cell_lists_what_the_real_cell_lists,
)
from benchmarks.tests.test_glm_moe_dsa import (  # noqa: E402,F401
    test_a_steps_counts_are_those_of_the_commit_that_followed_it,
    test_readers_know_the_bytes_a_step_has_to_read,
    test_real_configuration_is_the_catalogs_but_for_what_reduced_names,
    test_the_mix_is_the_issues_parameter_for_parameter,
    test_tiny_cell_lists_what_the_real_cell_lists
    as test_glm_tiny_cell_lists_what_the_real_cell_lists,
)
from benchmarks.tests.test_kimi_linear import (  # noqa: E402,F401
    test_readers_know_the_bytes_and_operations_a_step_and_a_slice_move,
    test_real_configuration_is_the_catalogs_but_for_what_reduced_names
    as test_kimi_real_configuration_is_the_catalogs_but_for_what_reduced_names,
    test_the_mix_is_the_issues_parameter_for_parameter
    as test_kimi_the_mix_is_the_issues_parameter_for_parameter,
    test_tiny_cell_lists_what_the_real_cell_lists
    as test_kimi_tiny_cell_lists_what_the_real_cell_lists,
)
from benchmarks.tests.test_minicpm_sala import (  # noqa: E402,F401
    test_readers_know_the_bytes_a_step_has_to_move,
    test_tiny_cell_lists_what_the_real_cell_lists
    as test_sala_tiny_cell_lists_what_the_real_cell_lists,
    test_traced_steps_are_matched_by_their_durations
    as test_sala_traced_steps_are_matched_by_their_durations,
)
from benchmarks.tests.test_overlap import (  # noqa: E402,F401
    test_nothing_to_read_is_none_not_an_error
    as test_overlap_nothing_to_read_is_none_not_an_error,
    test_share_of_the_steps_dispatched_ahead,
)
from benchmarks.tests.test_paged import (  # noqa: E402,F401
    test_a_traced_run_reads_the_scopes_time_and_the_rows_bytes,
    test_both_gpt_serve_cells_list_the_readers_and_no_other_cell_does,
    test_readers_find_nothing_without_the_scope_or_the_args,
    test_traced_steps_are_matched_by_their_durations
    as test_paged_traced_steps_are_matched_by_their_durations,
)
from benchmarks.tests.test_reference import (  # noqa: E402,F401
    setup,
    test_adamw_and_clip_match_optax,
    test_forward_matches_gpt_apply_in_float32,
    test_loss_and_gradients_match_the_programs_loss,
    test_lower_precisions_move_the_logits,
)
from benchmarks.tests.test_scopes import (  # noqa: E402,F401
    parsed,
    recorded_spans,
    test_annotation_and_sync_mark_place_a_span_alike,
    test_buckets_partition_the_busy_time_of_the_recorded_trace,
    test_decode_step_of_the_recorded_trace,
    test_nothing_to_read_is_none_not_an_error,
    test_pool_shapes_and_copies,
    test_probe_is_small_and_parsed_once,
    test_reduce_scopes_on_made_up_events,
    test_scope_names_on_a_path,
    test_self_times_add_up_to_the_union,
    test_train_step_of_the_recorded_trace,
)
from benchmarks.tests.test_spec import (  # noqa: E402,F401
    manifest,
    test_every_cell_config_and_mix_has_its_file,
    test_every_layer_metric_has_a_reader_that_agrees,
    test_run_refuses_to_run_off_the_chip,
)
from benchmarks.tests.test_stats import (  # noqa: E402,F401
    test_percentile_matches_numpy,
    test_quartile_spread_is_the_contracts,
    test_tokens_are_apportioned_to_the_window_by_time,
    test_whole_units_lie_inside_the_window,
)
from benchmarks.tests.test_trace import (  # noqa: E402,F401
    recorded,
    test_collectives_are_told_by_opcode_not_by_operands,
    test_merge,
    test_program_spans_name_gaps_through_the_sync_mark,
    test_recorded_trace_has_the_planes_the_reduction_reads,
    test_reduction_of_the_recorded_trace,
)
from benchmarks.tests.test_traffic import (  # noqa: E402,F401
    test_serve_requests_offer_every_seed_the_same_sizes,
    test_train_batches_repeat_per_seed_and_rows_differ,
)


def test_the_manifest_lists_the_reader_as_it_describes_itself():
    """``benchmarks/tests/test_overlap.py``'s test of this name, which
    tier-1 ran until PR 41, but for one line: it held ``decode_overlap_pct``
    to be the manifest's last per-layer entry ("appended, nothing moved"),
    and a later PR's entries go after it (the contract: new entries at the
    end of their lists). What it guarded stands: the entry is as its reader
    describes itself, lists the five serve cells it listed, and everything
    after it is a later PR's, appended (PR 41's, PR 43's and PR 47's, each
    its new cell's alone). The file under ``benchmarks/`` may not be edited by a PR that
    is no benchmark PR, so by hand that one assertion now fails
    (``CHANGES.md``, PR 41)."""
    import json

    from benchmarks.harness import spec
    from benchmarks.tests import test_overlap

    with open(spec.MANIFEST) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == test_overlap.NAME]
    reader = spec.load_module("layer_metrics", test_overlap.NAME)
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    assert entry["better"] == "higher"
    assert tuple(entry["workloads"]) == test_overlap.CELLS
    later = manifest["per_layer"][manifest["per_layer"].index(entry) + 1:]
    assert all(m["workloads"] in (
        ["kimi-linear-48b-a3b.serve-longdoc-closed"],
        ["glm-4.7-flash.train-8k"],
        ["trinity-large-preview.serve-mixed-closed"]) for m in later)
    for cell in entry["workloads"]:
        assert test_overlap.NAME in spec.load_cell(
            cell, manifest=manifest).per_layer


from benchmarks.tests.test_glm4_moe_lite import (  # noqa: E402,F401,I001
    compared,
    test_a_program_without_the_bias_in_its_selection_is_not_the_references,
    test_loss_and_every_leafs_gradient_are_the_references,
    test_program_with_bfloat16_parameters_is_not_correct,
    test_tiny_cell_runs_and_is_correct_and_the_bias_moved_by_the_loads,
)
