"""MySQL wire-protocol client against the in-process fake server
(minimysql), mirroring the reference's sqlmock strategy (SURVEY.md §4) but
through a real socket: framing, auth, text resultsets, errors."""

import dataclasses
import threading

import pytest

from gofr_tpu.datasource.minimysql import MiniMySQL
from gofr_tpu.datasource.mysql import (
    MySQLDB,
    MySQLError,
    escape_literal,
    interpolate,
    native_password_token,
)


@pytest.fixture(scope="module")
def server():
    with MiniMySQL(user="gofr", password="s3cret") as srv:
        yield srv


@pytest.fixture()
def db(server):
    d = MySQLDB("127.0.0.1", server.port, "gofr", "s3cret", "test")
    yield d
    d.close()


def test_handshake_and_ping(db):
    h = db.health_check()
    assert h.status == "UP"
    assert h.details["dialect"] == "mysql"
    assert "minimysql" in h.details["server_version"]


def test_wrong_password_denied(server):
    with pytest.raises(MySQLError, match="Access denied"):
        MySQLDB("127.0.0.1", server.port, "gofr", "wrong", "test")


def test_wrong_user_denied(server):
    with pytest.raises(MySQLError, match="Access denied"):
        MySQLDB("127.0.0.1", server.port, "intruder", "s3cret", "test")


def test_ddl_dml_and_text_resultset(db):
    db.execute("DROP TABLE IF EXISTS users")
    db.execute("CREATE TABLE users (id INTEGER, full_name TEXT, score REAL)")
    n = db.execute("INSERT INTO users VALUES (?, ?, ?)", 1, "Ada Lovelace", 9.5)
    assert n == 1
    db.execute_many("INSERT INTO users VALUES (?, ?, ?)",
                    [(2, "Grace Hopper", 8.25), (3, None, None)])
    rows = db.query("SELECT id, full_name, score FROM users ORDER BY id")
    assert [tuple(r) for r in rows] == [
        (1, "Ada Lovelace", 9.5), (2, "Grace Hopper", 8.25), (3, None, None),
    ]
    assert rows[0]["full_name"] == "Ada Lovelace"
    assert rows[0].keys() == ["id", "full_name", "score"]


def test_escaping_survives_round_trip(db):
    db.execute("DROP TABLE IF EXISTS notes")
    db.execute("CREATE TABLE notes (body TEXT)")
    evil = "Robert'); DROP TABLE notes;-- \" \\ \n über 🎉"
    db.execute("INSERT INTO notes VALUES (?)", evil)
    assert db.select_value("SELECT body FROM notes") == evil
    assert db.select_value("SELECT COUNT(*) FROM notes") == 1  # not dropped


def test_blob_bytes_vs_text_str(db):
    """BLOB (charset 63) round-trips as bytes; TEXT shares the wire type
    but decodes to str."""
    db.execute("DROP TABLE IF EXISTS b_t")
    db.execute("CREATE TABLE b_t (data BLOB)")
    blob = bytes(range(256))
    db.execute("INSERT INTO b_t VALUES (?)", blob)
    assert db.select_value("SELECT data FROM b_t") == blob


def test_connection_recovers_after_io_error(db, server):
    """An I/O error discards the desynced connection; the next call
    reconnects instead of reading stale packets."""
    db.execute("DROP TABLE IF EXISTS r_t")
    db.execute("CREATE TABLE r_t (v INTEGER)")
    db.execute("INSERT INTO r_t VALUES (1)")
    db._get_conn().sock.close()  # simulate a dropped connection
    with pytest.raises(Exception):
        db.query("SELECT v FROM r_t")
    assert db.select_value("SELECT v FROM r_t") == 1  # fresh connection


def test_connections_are_per_thread(db):
    """Transactions are connection-scoped in MySQL; per-thread connections
    keep one handler's BEGIN from swallowing another handler's statements
    (the sqlite DB uses the same strategy)."""
    conns = {}

    def grab(i):
        conns[i] = db._get_conn()
        db.select_value("SELECT 1")

    threads = [threading.Thread(target=grab, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert conns[0] is not conns[1]
    assert conns[0] is not db._get_conn()  # main thread has its own too


def test_select_into_dataclass(db):
    @dataclasses.dataclass
    class User:
        id: int = 0
        full_name: str = ""
        ignored: str = dataclasses.field(default="", metadata={"db": "nope"})

    db.execute("DROP TABLE IF EXISTS users2")
    db.execute("CREATE TABLE users2 (id INTEGER, full_name TEXT, extra TEXT)")
    db.execute("INSERT INTO users2 VALUES (?, ?, ?)", 7, "Katherine", "x")
    users = db.select(User, "SELECT * FROM users2")
    assert users == [User(id=7, full_name="Katherine")]
    one = db.select_one(User, "SELECT * FROM users2 WHERE id = ?", 7)
    assert one.full_name == "Katherine"
    assert db.select_one(User, "SELECT * FROM users2 WHERE id = ?", 404) is None


def test_transaction_commit_and_rollback(db):
    db.execute("DROP TABLE IF EXISTS tx_t")
    db.execute("CREATE TABLE tx_t (v INTEGER)")
    with db.begin() as tx:
        tx.execute("INSERT INTO tx_t VALUES (1)")
    assert db.select_value("SELECT COUNT(*) FROM tx_t") == 1
    with pytest.raises(RuntimeError, match="boom"):
        with db.begin() as tx:
            tx.execute("INSERT INTO tx_t VALUES (2)")
            raise RuntimeError("boom")
    assert db.select_value("SELECT COUNT(*) FROM tx_t") == 1  # rolled back


def test_sql_error_propagates(db):
    with pytest.raises(MySQLError, match="1064"):
        db.query("SELEKT broken")


def test_concurrent_queries_serialize_safely(db):
    db.execute("DROP TABLE IF EXISTS c_t")
    db.execute("CREATE TABLE c_t (v INTEGER)")
    errors = []

    def worker(i):
        try:
            db.execute("INSERT INTO c_t VALUES (?)", i)
            db.query("SELECT * FROM c_t")
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert db.select_value("SELECT COUNT(*) FROM c_t") == 8


def test_interpolation_and_escaping_units():
    assert interpolate("SELECT ?", [1]) == "SELECT 1"
    assert interpolate("SELECT '?', ?", ["x"]) == "SELECT '?', 'x'"
    assert escape_literal(None) == "NULL"
    assert escape_literal(True) == "1"
    assert escape_literal(b"\x01\xff") == "x'01ff'"
    assert escape_literal("a'b") == r"'a\'b'"
    with pytest.raises(MySQLError, match="not enough"):
        interpolate("? ?", [1])


def test_native_password_token_shape():
    tok = native_password_token("pw", b"\x01" * 20)
    assert len(tok) == 20
    assert native_password_token("", b"\x01" * 20) == b""


def test_container_wires_mysql(server, monkeypatch):
    """DB_DIALECT=mysql end-to-end through config+container (verdict #5's
    done-criterion)."""
    from gofr_tpu.config import EnvConfig
    from gofr_tpu.container import Container

    monkeypatch.setenv("DB_DIALECT", "mysql")
    monkeypatch.setenv("DB_HOST", "127.0.0.1")
    monkeypatch.setenv("DB_PORT", str(server.port))
    monkeypatch.setenv("DB_USER", "gofr")
    monkeypatch.setenv("DB_PASSWORD", "s3cret")
    monkeypatch.setenv("DB_NAME", "test")
    monkeypatch.delenv("REDIS_HOST", raising=False)
    monkeypatch.delenv("MODEL_NAME", raising=False)
    monkeypatch.delenv("TPU_ENABLED", raising=False)
    c = Container(EnvConfig())
    assert c.db is not None
    assert c.db.execute("SELECT 1") == 0  # resultset path exercised below
    assert c.db.select_value("SELECT 41 + 1") == 42
    health = c.health()
    assert health["details"]["sql"]["status"] == "UP"
    c.close()


def test_container_degrades_on_bad_mysql(monkeypatch):
    from gofr_tpu.config import EnvConfig
    from gofr_tpu.container import Container

    monkeypatch.setenv("DB_DIALECT", "mysql")
    monkeypatch.setenv("DB_HOST", "127.0.0.1")
    monkeypatch.setenv("DB_PORT", "1")  # nothing listens
    monkeypatch.setenv("DB_NAME", "test")
    monkeypatch.delenv("REDIS_HOST", raising=False)
    monkeypatch.delenv("MODEL_NAME", raising=False)
    monkeypatch.delenv("TPU_ENABLED", raising=False)
    c = Container(EnvConfig())
    assert c.db is None  # logged, not fatal (container.go:80-85 parity)
    c.close()


# -- caching_sha2_password (MySQL 8 default) ---------------------------------

def test_sha2_fast_auth_is_the_default():
    """The fixture server advertises caching_sha2_password (stock MySQL 8),
    so the happy path above already runs the sha2 scramble; this pins it."""
    with MiniMySQL(user="u", password="pw") as srv:
        assert srv.auth_plugin == "caching_sha2_password"
        db = MySQLDB("127.0.0.1", srv.port, "u", "pw", "")
        assert db.health_check().status == "UP"
        db.close()


def test_sha2_full_auth_rsa_exchange():
    """Cache-miss path: server demands perform_full_authentication; the
    client fetches the RSA key and sends the nonce-whitened password
    encrypted — over plain TCP, as go-sql-driver does without TLS."""
    with MiniMySQL(user="u", password="hunter2", full_auth=True) as srv:
        db = MySQLDB("127.0.0.1", srv.port, "u", "hunter2", "")
        assert db.select_value("select 41 + 1") == 42
        db.close()


def test_sha2_full_auth_wrong_password_denied():
    with MiniMySQL(user="u", password="right", full_auth=True) as srv:
        with pytest.raises(MySQLError) as exc:
            MySQLDB("127.0.0.1", srv.port, "u", "wrong", "")
        assert exc.value.code == 1045


def test_auth_switch_to_native_password():
    """Server advertises caching_sha2 but switches the account to
    mysql_native_password — the client must check the plugin NAME in the
    AuthSwitchRequest, not resend the old plugin's token."""
    with MiniMySQL(user="u", password="pw",
                   switch_to="mysql_native_password") as srv:
        db = MySQLDB("127.0.0.1", srv.port, "u", "pw", "")
        assert db.select_value("select 7") == 7
        db.close()


def test_auth_switch_to_sha2():
    with MiniMySQL(user="u", password="pw",
                   auth_plugin="mysql_native_password",
                   switch_to="caching_sha2_password") as srv:
        db = MySQLDB("127.0.0.1", srv.port, "u", "pw", "")
        assert db.select_value("select 7") == 7
        db.close()


def test_unknown_plugin_rejected_with_clear_error():
    with MiniMySQL(user="u", password="pw",
                   auth_plugin="sha256_password") as srv:
        with pytest.raises(MySQLError) as exc:
            MySQLDB("127.0.0.1", srv.port, "u", "pw", "")
        assert exc.value.code == 2059
        assert "sha256_password" in str(exc.value)


def test_sha2_empty_password():
    with MiniMySQL(user="u", password="") as srv:
        db = MySQLDB("127.0.0.1", srv.port, "u", "", "")
        assert db.select_value("select 1") == 1
        db.close()
