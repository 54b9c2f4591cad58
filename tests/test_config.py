"""Config registry: argv channel, overrides, pull channel (SURVEY §5)."""

import pytest

from uda_tpu.utils.config import Config, FLAGS
from uda_tpu.utils.errors import ConfigError


def test_defaults():
    cfg = Config()
    assert cfg.get("mapred.rdma.wqe.per.conn") == 256
    assert cfg.get("mapred.rdma.cma.port") == 9011
    assert cfg.get("mapred.rdma.buf.size") == 1024
    assert cfg.get("mapred.netmerger.merge.approach") == 1
    assert cfg.get("mapred.rdma.num.parallel.lpqs") == 0


def test_argv_channel():
    # the reference's getopt short options (C2JNexus.cc:43-137)
    cfg = Config.from_argv(["-w", "128", "-r", "9012", "-a", "2",
                            "-m", "0", "-g", "/tmp/l", "-t", "5", "-s", "512"])
    assert cfg.get("mapred.rdma.wqe.per.conn") == 128
    assert cfg.get("mapred.rdma.cma.port") == 9012
    assert cfg.get("mapred.netmerger.merge.approach") == 2
    assert cfg.get("uda.log.dir") == "/tmp/l"
    assert cfg.get("uda.log.level") == 5
    assert cfg.get("mapred.rdma.buf.size") == 512


def test_argv_errors():
    with pytest.raises(ConfigError):
        Config.from_argv(["-z", "1"])
    with pytest.raises(ConfigError):
        Config.from_argv(["-w"])


def test_pull_channel():
    pulled = {}

    def source(key, default):
        pulled[key] = default
        return "2048" if key == "mapred.rdma.buf.size" else ""

    cfg = Config(conf_source=source)
    assert cfg.get("mapred.rdma.buf.size") == 2048
    assert pulled["mapred.rdma.buf.size"] == "1024"  # default passed through
    # empty pull -> default
    assert cfg.get("mapred.rdma.cma.port") == 9011


def test_bool_coercion_and_unknown():
    cfg = Config({"mapred.rdma.developer.mode": "true"})
    assert cfg.get("mapred.rdma.developer.mode") is True
    with pytest.raises(ConfigError):
        cfg.get("no.such.key")
    assert cfg.get("no.such.key", default=7) == 7


def test_flag_inventory_complete():
    # every reference flag from SURVEY §5 is declared
    for key in [
        "mapred.rdma.wqe.per.conn", "mapred.rdma.cma.port",
        "mapred.netmerger.merge.approach", "mapred.rdma.buf.size",
        "mapred.rdma.buf.size.min", "mapred.rdma.shuffle.total.size",
        "mapred.job.shuffle.input.buffer.percent",
        "mapred.netmerger.hybrid.lpq.size", "mapred.rdma.num.parallel.lpqs",
        "mapred.rdma.compression.buffer.ratio",
        "mapred.uda.log.to.unique.file",
        "mapred.uda.provider.blocked.threads.per.disk",
        "mapred.rdma.developer.mode",
    ]:
        assert key in FLAGS, key


@pytest.mark.parametrize("name", ("uda.tpu.stage.pipeline",
                                  "uda.tpu.online.stagers",
                                  "uda.tpu.merge.overlap",
                                  "uda.tpu.merge.two_phase"))
def test_the_flags_of_the_deleted_merge_routes_are_gone(name):
    """PR 45: one staging architecture and no plain fetch-all route —
    the flags that chose are not declared and not documented."""
    import os

    assert name not in FLAGS
    with pytest.raises(ConfigError):
        Config().get(name)
    readme = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "README.md")
    with open(readme, encoding="utf-8") as f:
        assert name not in f.read()
