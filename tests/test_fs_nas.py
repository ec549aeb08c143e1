"""io/fs abstraction + light-NAS tests.

Reference analogs: framework/io/fs.cc localfs ops; fleet utils HDFSClient
(hadoop-CLI command construction — exercised here against a stub hadoop
binary, the same way the reference unit-tests it without a cluster);
contrib/slim light_nas sa_controller.
"""

import os
import stat

import pytest

from paddle_tpu import slim
from paddle_tpu.fs import HDFSClient, LocalFS, get_fs


class TestLocalFS:
    def test_roundtrip(self, tmp_path):
        fs = LocalFS()
        d = str(tmp_path / "a/b")
        fs.mkdirs(d)
        assert fs.is_dir(d)
        p = os.path.join(d, "x.bin")
        with fs.open_write(p) as f:
            f.write(b"hello")
        assert fs.is_file(p) and fs.is_exist(p)
        with fs.open_read(p) as f:
            assert f.read() == b"hello"
        dirs, files = fs.ls_dir(str(tmp_path / "a"))
        assert dirs == ["b"] and files == []
        fs.rename(p, os.path.join(d, "y.bin"))
        assert not fs.is_exist(p)
        fs.delete(str(tmp_path / "a"))
        assert not fs.is_exist(str(tmp_path / "a"))

    def test_get_fs_routing(self, tmp_path):
        fs, p = get_fs(str(tmp_path))
        assert isinstance(fs, LocalFS) and p == str(tmp_path)
        fs, p = get_fs("file:///x/y")
        assert isinstance(fs, LocalFS) and p == "/x/y"
        fs, p = get_fs("hdfs://ns/a", hadoop_bin="nope")
        assert isinstance(fs, HDFSClient) and p == "hdfs://ns/a"


def _stub_hadoop(tmp_path):
    """A fake `hadoop` that logs its argv and emulates a tiny fs -ls."""
    path = tmp_path / "hadoop"
    log = tmp_path / "calls.log"
    path.write_text(f"""#!/bin/sh
echo "$@" >> {log}
case " $* " in
  *" -ls "*)
    echo "Found 2 items"
    echo "drwxr-xr-x   - u g          0 2026-01-01 00:00 hdfs://ns/a/sub"
    echo "-rw-r--r--   3 u g       1234 2026-01-01 00:00 hdfs://ns/a/f.txt"
    ;;
esac
exit 0
""")
    path.chmod(path.stat().st_mode | stat.S_IEXEC)
    return str(path), log


class TestHDFSClient:
    def test_command_construction_and_parsing(self, tmp_path):
        binpath, log = _stub_hadoop(tmp_path)
        c = HDFSClient(hadoop_bin=binpath,
                       configs={"fs.defaultFS": "hdfs://ns"})
        assert c.is_exist("hdfs://ns/a")
        c.mkdirs("hdfs://ns/a/b")
        c.upload("/tmp/x", "hdfs://ns/a/x")
        dirs, files = c.ls_dir("hdfs://ns/a")
        assert dirs == ["sub"] and files == ["f.txt"]
        calls = log.read_text().splitlines()
        assert calls[0].startswith("fs -D fs.defaultFS=hdfs://ns -test -e")
        assert "-mkdir -p hdfs://ns/a/b" in calls[1]
        assert "-put -f /tmp/x hdfs://ns/a/x" in calls[2]

    def test_failure_raises_with_stderr(self, tmp_path):
        path = tmp_path / "hadoop"
        path.write_text("#!/bin/sh\necho boom >&2\nexit 1\n")
        path.chmod(path.stat().st_mode | stat.S_IEXEC)
        c = HDFSClient(hadoop_bin=str(path))
        with pytest.raises(IOError, match="boom"):
            c.mkdirs("hdfs://ns/x")


class TestSaSearch:
    def test_finds_optimum_of_separable_objective(self):
        space = {"a": [1, 2, 3, 4], "b": [10, 20, 30], "c": ["x", "y"]}

        def reward(cfg):
            return -abs(cfg["a"] - 3) - abs(cfg["b"] - 20) / 10 \
                + (1.0 if cfg["c"] == "y" else 0.0)

        best, best_r, hist = slim.sa_search(space, reward, iters=200,
                                            seed=0)
        assert best == {"a": 3, "b": 20, "c": "y"}
        assert best_r == pytest.approx(1.0)
        assert len(hist) == 201

    def test_invalid_init_rejected(self):
        with pytest.raises(ValueError):
            slim.sa_search({"a": [1, 2]}, lambda c: 0.0,
                           init={"a": 99}, iters=1)
