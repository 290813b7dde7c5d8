"""The yardstick's traffic is frozen: `populations/plain.py` emits, byte for
byte, what `harness/cluster_gen.py` emitted through the client and the
checks of the tree before populations (PR 25's), for seeds 0 and 3 on both
configurations, at full and at rehearsal size. The digests are sha256 over
the event lines in the order they are sent."""

import hashlib

import pytest

from harness import spec

#: {config[/rehearsal]/seed: {what: sha256}}, taken from the parent tree
GOLDEN = {
 "basic-5000n/0": {
  "nodes": "a0e570d5969e71cf20654728dc19a0717237aa5e9893fbabe694bd7fbd773ed8",
  "prefill/1000": "b28e172df07ba8f4193868a54127d606fa43fa78e9f7a727d1040b6303291e6c",
  "prefill/50000": "faef6ca47735e67a274f293db69b5e0f05447bdca0053b7a2db46833c25d365b",
  "arrivals": "a86c0c622f075b7273e7b18afb8ba69e225303eb526f9ae6c41be92ba3df48ae",
  "warm/512": "1c6d4c7a0de3d860f5541fda199bd8e93d8b9fff9ade2f719501602af909c0e7",
  "probe/512": "15ad0052580566a423d3ed32d34eb872f80fbf5274cc860862f58e24588e1b3c"
 },
 "basic-5000n/3": {
  "nodes": "524a1f95c3ad075b8495fd618267b418fa6f648510aa57f4c5f6a3488d96dc01",
  "prefill/1000": "483a4d4e4bab802f021058a97184fa8303bdec01003b310bf8b04ea26512899f",
  "prefill/50000": "0fb0ea146a3dc90ce119f8754631e88a0ced23bc5f6599137eff4abdeef2607d",
  "arrivals": "d675069e321b1d39b21ebe2bd8b864a6c16c1e129b64722310dc23839d6eb7dd",
  "warm/512": "431e8efe2b98ff9878ee2c01e33a844e4a60bfc0b36e2a865832f8a8e0d5a82e",
  "probe/512": "da90757eae6cc93d912bd174de975e0a7346401ba94dcf7cc793aebcae97afaa"
 },
 "basic-5000n/rehearsal/0": {
  "nodes": "c71fe4dedadcdbe3d9d6e3fd7d3f0078da441f34c54227a62c4118cdbe05b818",
  "prefill/20": "b3bef6951d66ed1c784d6f7352558d1fe70e7958df198790fb7fb9f11f226984",
  "prefill/200": "36097149da014d3c8c17992e3539bfe01f95f88669feb0884d2b5e3d6d18096f",
  "arrivals": "7dafeb7101a9bf82f7f447fff22a802673254e42ad9a6ede0cb1b7243d03cde6",
  "warm/512": "1c6d4c7a0de3d860f5541fda199bd8e93d8b9fff9ade2f719501602af909c0e7",
  "probe/512": "15ad0052580566a423d3ed32d34eb872f80fbf5274cc860862f58e24588e1b3c"
 },
 "basic-5000n/rehearsal/3": {
  "nodes": "e48bd09ae5c03268bef722977ce8e123e99ffa787b7de667245aec4c3f032c7c",
  "prefill/20": "923e9b645993a5245a326fc7c592441da868964d5c2e096636116410e0b64791",
  "prefill/200": "07ea6e03ba390833db58da1100bd8db0590160103455c8c4bebd2660729c3f4b",
  "arrivals": "638754139da86594ccd7740e9fb6be7adfb933489046b8a0e989bf8d8b15afde",
  "warm/512": "431e8efe2b98ff9878ee2c01e33a844e4a60bfc0b36e2a865832f8a8e0d5a82e",
  "probe/512": "da90757eae6cc93d912bd174de975e0a7346401ba94dcf7cc793aebcae97afaa"
 },
 "trimaran-5000n/0": {
  "nodes": "a0e570d5969e71cf20654728dc19a0717237aa5e9893fbabe694bd7fbd773ed8",
  "prefill/1000": "b28e172df07ba8f4193868a54127d606fa43fa78e9f7a727d1040b6303291e6c",
  "prefill/50000": "faef6ca47735e67a274f293db69b5e0f05447bdca0053b7a2db46833c25d365b",
  "arrivals": "a86c0c622f075b7273e7b18afb8ba69e225303eb526f9ae6c41be92ba3df48ae",
  "side/node_metrics/0": "4599dc25bbd41b8a7e48469cca6c4b8beac90ceb6bae57ab41905e53ed94474d",
  "side/node_metrics/1": "d16e44233905199b3b25e5c578c73cd58dfea88a7e1d4f19efe1e06e0341e108",
  "warm/512": "1c6d4c7a0de3d860f5541fda199bd8e93d8b9fff9ade2f719501602af909c0e7",
  "probe/512": "15ad0052580566a423d3ed32d34eb872f80fbf5274cc860862f58e24588e1b3c"
 },
 "trimaran-5000n/3": {
  "nodes": "524a1f95c3ad075b8495fd618267b418fa6f648510aa57f4c5f6a3488d96dc01",
  "prefill/1000": "483a4d4e4bab802f021058a97184fa8303bdec01003b310bf8b04ea26512899f",
  "prefill/50000": "0fb0ea146a3dc90ce119f8754631e88a0ced23bc5f6599137eff4abdeef2607d",
  "arrivals": "d675069e321b1d39b21ebe2bd8b864a6c16c1e129b64722310dc23839d6eb7dd",
  "side/node_metrics/0": "391feed58743e0f3e6036736b1b2f21aef5862031b3271b1634eed615996e510",
  "side/node_metrics/1": "b7c3a43f83bfcfa89bc9b0c3c743a7d4050e5ec1167b056ae6eeb5baebad5e68",
  "warm/512": "431e8efe2b98ff9878ee2c01e33a844e4a60bfc0b36e2a865832f8a8e0d5a82e",
  "probe/512": "da90757eae6cc93d912bd174de975e0a7346401ba94dcf7cc793aebcae97afaa"
 },
 "trimaran-5000n/rehearsal/0": {
  "nodes": "c71fe4dedadcdbe3d9d6e3fd7d3f0078da441f34c54227a62c4118cdbe05b818",
  "prefill/20": "b3bef6951d66ed1c784d6f7352558d1fe70e7958df198790fb7fb9f11f226984",
  "prefill/200": "36097149da014d3c8c17992e3539bfe01f95f88669feb0884d2b5e3d6d18096f",
  "arrivals": "7dafeb7101a9bf82f7f447fff22a802673254e42ad9a6ede0cb1b7243d03cde6",
  "side/node_metrics/0": "d148cf996ed0f305e103550798e9b93ca866adf11947c737557ca09441e5a6b1",
  "side/node_metrics/1": "57cb211c7b70b70aba703103b9b3b4e63a9e8fd100e43f53f8280b27b24646cd",
  "warm/512": "1c6d4c7a0de3d860f5541fda199bd8e93d8b9fff9ade2f719501602af909c0e7",
  "probe/512": "15ad0052580566a423d3ed32d34eb872f80fbf5274cc860862f58e24588e1b3c"
 },
 "trimaran-5000n/rehearsal/3": {
  "nodes": "e48bd09ae5c03268bef722977ce8e123e99ffa787b7de667245aec4c3f032c7c",
  "prefill/20": "923e9b645993a5245a326fc7c592441da868964d5c2e096636116410e0b64791",
  "prefill/200": "07ea6e03ba390833db58da1100bd8db0590160103455c8c4bebd2660729c3f4b",
  "arrivals": "638754139da86594ccd7740e9fb6be7adfb933489046b8a0e989bf8d8b15afde",
  "side/node_metrics/0": "c3f05c50a57b770a3ded8c9b941ebec44f373c8cd181e1a0c9518a56e918754b",
  "side/node_metrics/1": "4eef5178895d7499643f2c6249a590a7d17af3e961731e29c5ef7a57e89ee8a1",
  "warm/512": "431e8efe2b98ff9878ee2c01e33a844e4a60bfc0b36e2a865832f8a8e0d5a82e",
  "probe/512": "da90757eae6cc93d912bd174de975e0a7346401ba94dcf7cc793aebcae97afaa"
 }
}


def _sha(lines) -> str:
    digest = hashlib.sha256()
    for line in lines:
        digest.update(line)
    return digest.hexdigest()


def _unit_lines(population, stream: str, count: int):
    for index in range(count):
        unit = population.unit(stream, index)
        assert unit.binds and not unit.head and len(unit.pods) == 1
        yield unit.pods[0]


def digests(config: dict, seed: int, prefills, arrivals: int) -> dict:
    """What a population emits for one configuration and seed. Each piece
    comes from a population of its own, as the client and the harness each
    build theirs."""
    out = {"nodes": _sha(spec.population(config, seed).nodes())}
    for count in prefills:
        out[f"prefill/{count}"] = _sha(
            line for unit in spec.population(config, seed).prefill(count)
            for line in unit.head + unit.pods
        )
    population = spec.population(config, seed)
    out["arrivals"] = _sha(_unit_lines(population, "arrivals", arrivals))
    for side in config.get("feed_side_events", []):
        for issue in (0, 1):
            out[f"side/{side['kind']}/{issue}"] = _sha(
                [population.side(side, issue)]
            )
    for prefix in ("warm", "probe"):
        out[f"{prefix}/512"] = _sha(
            _unit_lines(spec.population(config, seed), f"{prefix}/512", 512)
        )
    return out


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_plain_population_emits_the_parents_bytes(key):
    name, *rehearsal, seed = key.split("/")
    config = spec.Cell(f"{name}.steady", rehearse=bool(rehearsal)).config
    assert config.get("population", "plain") == "plain"
    sizes = ((20, 200), 1000) if rehearsal else ((1000, 50000), 10000)
    assert digests(config, int(seed), *sizes) == GOLDEN[key]


def test_objects_are_empty_and_units_carry_their_own_removal():
    config = spec.Cell("basic-5000n.backlog", rehearse=True).config
    population = spec.population(config, 3)
    assert list(population.objects()) == []
    unit = population.unit("arrivals", 7)
    assert unit.uids == ("default/a-0000007",)
    assert unit.removal == (b'{"op":"delete_pod","name":"a-0000007"}\n',)
    wave = population.unit("warm/64", 5)
    assert wave.uids == ("default/warm-64-000005",)
    with pytest.raises(ValueError):
        population.side({"kind": "nrt_refresh"}, 0)


def test_interleaved_streams_draw_independently():
    config = spec.Cell("basic-5000n.backlog", rehearse=True).config
    one = spec.population(config, 0)
    expected = [one.unit("arrivals", i).pods for i in range(50)]
    two = spec.population(config, 0)
    got = []
    for i in range(50):
        two.unit("probe/8", i)
        got.append(two.unit("arrivals", i).pods)
    assert got == expected
