"""tools/check_recorder_registry wired into tier-1: the static recorder
check must stay clean, and its validators must actually detect rot."""

from tools.check_recorder_registry import (
    NAME_RE,
    TAG_VOCAB,
    doc_table_names,
    main,
    run_checks,
)


class TestRegistryClean:
    def test_run_checks_clean(self):
        errors, notes = run_checks()
        assert errors == []
        assert notes  # declaration/doc counts reported

    def test_main_exit_zero(self, capsys):
        assert main() == 0
        assert "clean" in capsys.readouterr().out


class TestValidators:
    def test_naming_rule(self):
        assert NAME_RE.match("storage.write")
        assert NAME_RE.match("kvcache.gc.removes")
        assert not NAME_RE.match("plainname")       # no subsystem
        assert not NAME_RE.match("Storage.Write")   # case
        assert not NAME_RE.match("a.b-c")           # bad char

    def test_vocabulary_is_the_contract(self):
        # the fixed tag-key vocabulary of the ISSUE, plus the identity
        # keys the codebase already stamps
        assert {"service", "class", "tenant", "chain"} <= TAG_VOCAB

    def test_doc_table_parse_scoped_to_metric_section(self):
        names = doc_table_names()
        assert "storage.write" in names
        assert "qos.admitted" in names
        # other tables in the doc (stage glossary, knobs) must NOT leak
        assert "issue" not in names
        assert "trace.sample_rate" not in names

    def test_the_head_partial_counters_are_declared_and_documented(self):
        """How often the fresh-partial-stripe batch engages: both ways of
        a head-partial write have a recorder and a row."""
        from tools.check_recorder_registry import collect_declarations

        want = {"ec.head_partial_batched", "ec.head_partial_ladder"}
        assert want <= set(doc_table_names())
        declared = {d[0]: d[3] for d in collect_declarations()[0]}
        assert want <= set(declared)
