"""The reader of ``sized_compact_share.select``: found by name, reads the
server's ``compact`` counter, and is silent where the counter is absent."""
import harness


def test_sized_compact_share_reads_the_compact_counter(checkout):
    read = harness.reader(checkout / "bench", "sized_compact_share.select")
    run = harness.Run(stats={"compact": {"sized": 510, "full": 0}})
    assert read(run) == 100.0
    assert read(harness.Run(stats={"compact": {"sized": 1, "full": 3}})) \
        == 25.0
    assert read(harness.Run(stats={"transfer": {}})) is None
    assert read(harness.Run(stats={"compact": {"sized": 0, "full": 0}})) \
        is None
