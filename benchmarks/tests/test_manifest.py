"""The manifest check: BENCHMARK.json and the data files it names."""
import copy
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.manifest import Manifest, check_values  # noqa: E402


def test_the_committed_manifest_is_sound():
    assert Manifest().check() == []


def broken(edit):
    man = Manifest()
    man.doc = copy.deepcopy(man.doc)
    edit(man.doc)
    return man.check()


def test_names_and_units_in_the_allowed_characters():
    def bad_name(doc):
        doc["per_layer"][0]["name"] = "replays in window"
    assert any("is not a name" in f for f in broken(bad_name))

    def bad_unit(doc):
        doc["end_to_end"][0]["unit"] = "blocks per second"
    assert any("unit" in f for f in broken(bad_unit))

    def greek(doc):
        doc["per_layer"][2]["unit"] = "µs/block"
    assert any("unit" in f for f in broken(greek))


def test_every_layer_metric_moves_a_metric_its_cells_report():
    def no_target(doc):
        doc["per_layer"][0]["moves"] = "verdict_p95_ms"
    assert any("no end-to-end metric" in f for f in broken(no_target))

    def target_only_elsewhere(doc):
        cells = [w["name"] for w in doc["workloads"]]
        doc["end_to_end"][0]["workloads"] = cells[:1]
        doc["end_to_end"].append(
            {"name": "other", "unit": "s", "better": "lower", "bound": 0.05,
             "source": "host_clock"})
    faults = broken(target_only_elsewhere)
    if len(Manifest().doc["workloads"]) > 1:
        assert any("does not report" in f for f in faults)


def test_a_share_is_a_percentage():
    def frac(doc):
        for m in doc["per_layer"]:
            if m["name"].endswith("_share"):
                m["unit"] = "frac"
                return
    assert any("percentage" in f for f in broken(frac))
    ok = {"device_idle_share": {"value": 86.6, "unit": "%"},
          "blocks_per_s": {"value": 2000.0, "unit": "blocks/s"}}
    assert check_values(ok) == []
    assert check_values({"lane_pad_share": {"value": 184.9, "unit": "%"}})
    assert check_values({"disk_hidden_share": {"value": -0.1, "unit": "%"}})


def test_file_and_manifest_must_agree():
    def other_layer(doc):
        doc["per_layer"][0]["layer"] = "somewhere else"
    assert any("in its file" in f for f in broken(other_layer))
