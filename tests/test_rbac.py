"""RBAC: roles, user groups, workspace-scoped assignments, enforcement.

Drives a C++ master started with --auth-required --rbac over REST,
≈ the reference's e2e_tests/tests/cluster/test_rbac.py against
master/internal/rbac + usergroup. Role model: a strict hierarchy
Viewer < Editor < WorkspaceAdmin < ClusterAdmin, assignable to users or
groups at global scope or per-workspace.
"""
import time
from pathlib import Path

import pytest

from tests.test_platform import build_binaries, start_master

from determined_clone_tpu.api.client import MasterError, MasterSession

REPO = Path(__file__).resolve().parent.parent


def login_as(master, username, password=""):
    s = MasterSession("127.0.0.1", master["port"], timeout=10, retries=2)
    s.login(username, password)
    return s


@pytest.fixture(scope="module")
def master(tmp_path_factory):
    if not build_binaries():
        pytest.skip("C++ master build unavailable")
    tmp = tmp_path_factory.mktemp("rbac")
    proc, session, port = start_master(tmp, "--auth-required", "--rbac")
    session.login("admin")
    yield {"session": session, "tmp": tmp, "port": port, "proc": proc}
    proc.kill()
    proc.wait(timeout=10)


def test_roles_are_static_hierarchy(master):
    roles = {r["name"]: r["rank"] for r in master["session"].list_roles()}
    assert roles == {"Viewer": 1, "Editor": 2, "WorkspaceAdmin": 3,
                     "ClusterAdmin": 4}


def test_admin_flag_is_cluster_admin(master):
    me = master["session"].my_permissions()
    assert me["role"] == "ClusterAdmin" and me["rank"] == 4
    assert me["enforced"] is True


def _user_id(admin, username):
    """A user's id; the user is made here where an earlier test of this
    module has not made it (xdist's load distribution may split the
    module, and each part has a master of its own)."""
    for u in admin.list_users():
        if u["username"] == username:
            return u["id"]
    return admin.create_user(username, "pw")["id"]


def _nobody_id(admin):
    """The unassigned user's id."""
    return _user_id(admin, "nobody")


def test_unassigned_user_cannot_mutate(master):
    admin = master["session"]
    _nobody_id(admin)
    nobody = login_as(master, "nobody", "pw")
    assert nobody.my_permissions()["rank"] == 0
    with pytest.raises(MasterError) as err:
        nobody.create_experiment({"name": "x", "entrypoint": "x:Y"})
    assert err.value.status == 403
    with pytest.raises(MasterError) as err:
        nobody.create_workspace("nope")
    assert err.value.status == 403
    # reads remain session-gated only (any authenticated user)
    assert isinstance(nobody.list_experiments(), list)


def test_workspace_scoped_editor_via_group(master):
    admin = master["session"]
    ws = admin.create_workspace("ml-team")
    alice = admin.create_user("alice", "pw")
    group = admin.create_group("ml-editors", user_ids=[alice["id"]])
    admin.assign_role("Editor", group_id=group["id"], workspace_id=ws["id"])

    s = login_as(master, "alice", "pw")
    assert s.my_permissions(ws["id"])["role"] == "Editor"
    assert s.my_permissions()["rank"] == 0  # scope does not leak globally

    # can create experiments in ml-team...
    exp = s.create_experiment({
        "name": "ok", "entrypoint": "x:Y", "workspace": "ml-team",
        "searcher": {"name": "single", "metric": "loss",
                     "max_length": {"batches": 1}},
    })
    assert exp["workspace"] == "ml-team"
    s.kill_experiment(exp["id"])  # Editor can kill in-scope

    # ...but not in Uncategorized (different scope)
    with pytest.raises(MasterError) as err:
        s.create_experiment({"name": "no", "entrypoint": "x:Y"})
    assert err.value.status == 403

    # removing alice from the group revokes the grant
    admin.update_group_members(group["id"], remove=[alice["id"]])
    with pytest.raises(MasterError) as err:
        s.create_experiment({"name": "no2", "entrypoint": "x:Y",
                             "workspace": "ml-team"})
    assert err.value.status == 403
    admin.update_group_members(group["id"], add=[alice["id"]])


def _workspace_id(admin, name):
    """A workspace's id; made here where an earlier test of this module
    has not made it (as ``_user_id``: xdist may split the module)."""
    for w in admin.list_workspaces():
        if w["name"] == name:
            return w["id"]
    return admin.create_workspace(name)["id"]


def test_editor_cannot_admin_workspace(master):
    admin = master["session"]
    ws_id = _workspace_id(admin, "ml-team")
    alice_id = _user_id(admin, "alice")
    alice = login_as(master, "alice", "pw")
    # archive needs WorkspaceAdmin
    with pytest.raises(MasterError) as err:
        alice.post(f"/api/v1/workspaces/{ws_id}/archive")
    assert err.value.status == 403
    admin.assign_role("WorkspaceAdmin", user_id=alice_id,
                      workspace_id=ws_id)
    alice.post(f"/api/v1/workspaces/{ws_id}/archive")
    alice.post(f"/api/v1/workspaces/{ws_id}/unarchive")


def test_global_viewer_cannot_create(master):
    admin = master["session"]
    bob = admin.create_user("bob", "pw")
    admin.assign_role("Viewer", user_id=bob["id"])
    s = login_as(master, "bob", "pw")
    assert s.my_permissions()["role"] == "Viewer"
    with pytest.raises(MasterError) as err:
        s.create_model("m-bob")
    assert err.value.status == 403


def test_only_cluster_admin_manages_assignments(master):
    _user_id(master["session"], "alice")  # no global role either way
    alice = login_as(master, "alice", "pw")
    with pytest.raises(MasterError) as err:
        alice.assign_role("Editor", user_id=1)
    assert err.value.status == 403
    with pytest.raises(MasterError) as err:
        alice.create_group("sneaky")
    assert err.value.status == 403


def test_ntsc_tasks_are_gated(master):
    admin = master["session"]
    ed = admin.create_user("ed", "pw")
    admin.assign_role("Editor", user_id=ed["id"])  # global scope

    _nobody_id(admin)
    nobody = login_as(master, "nobody", "pw")
    with pytest.raises(MasterError) as err:
        nobody.create_task("command", cmd=["echo", "hi"])
    assert err.value.status == 403

    s = login_as(master, "ed", "pw")
    task = s.create_task("command", cmd=["echo", "hi"], owner="ed")
    # a roleless user cannot kill someone else's task...
    with pytest.raises(MasterError) as err:
        nobody.kill_task(task["id"])
    assert err.value.status == 403
    # ...but the owner can, even without a global role on that route
    s.kill_task(task["id"])


def test_role_granted_cluster_admin_manages_users(master):
    admin = master["session"]
    root2 = admin.create_user("root2", "pw")
    admin.assign_role("ClusterAdmin", user_id=root2["id"])
    s = login_as(master, "root2", "pw")
    made = s.create_user("made-by-root2", "pw")
    assert made["username"] == "made-by-root2"
    g = s.create_group("root2-group")
    s.delete_group(g["id"])


def test_member_add_is_atomic(master):
    admin = master["session"]
    g = admin.create_group("atomic")
    uid = _nobody_id(admin)
    with pytest.raises(MasterError) as err:
        admin.update_group_members(g["id"], add=[uid, 999999])
    assert err.value.status == 400
    # the valid id must NOT have been applied by the failed request
    assert admin.list_groups()[-1]["user_ids"] == [] or not any(
        grp["id"] == g["id"] and uid in grp["user_ids"]
        for grp in admin.list_groups())
    admin.delete_group(g["id"])


def test_assignment_validation(master):
    admin = master["session"]
    with pytest.raises(MasterError):
        admin.assign_role("NotARole", user_id=1)
    with pytest.raises(MasterError):
        admin.assign_role("Editor")  # no principal
    with pytest.raises(MasterError):
        admin.assign_role("Editor", user_id=1, group_id=1)  # both
    with pytest.raises(MasterError):
        admin.assign_role("ClusterAdmin", user_id=1, workspace_id=1)
    with pytest.raises(MasterError):
        admin.assign_role("Editor", user_id=999999)
    # exact duplicates are rejected — deleting one of two identical rows
    # would leave the grant silently active
    dup = admin.assign_role("Viewer", user_id=1)
    with pytest.raises(MasterError) as err:
        admin.assign_role("Viewer", user_id=1)
    assert "already exists" in str(err.value)
    admin.remove_role_assignment(dup["id"])


def test_deleting_group_revokes_roles(master):
    admin = master["session"]
    carol = admin.create_user("carol", "pw")
    g = admin.create_group("temps", user_ids=[carol["id"]])
    admin.assign_role("Editor", group_id=g["id"])
    s = login_as(master, "carol", "pw")
    assert s.my_permissions()["role"] == "Editor"
    admin.delete_group(g["id"])
    assert s.my_permissions()["rank"] == 0
    assert not any(a["group_id"] == g["id"]
                   for a in admin.list_role_assignments())


def test_workspace_delete_revokes_scoped_assignments(master):
    admin = master["session"]
    ws = admin.create_workspace("ephemeral")
    dave = admin.create_user("dave", "pw")
    a = admin.assign_role("Editor", user_id=dave["id"],
                          workspace_id=ws["id"])
    admin.request("DELETE", f"/api/v1/workspaces/{ws['id']}")
    assert not any(x["id"] == a["id"]
                   for x in admin.list_role_assignments())


def test_rbac_state_survives_restart(master):
    admin = master["session"]
    _nobody_id(admin)
    if not admin.list_groups():  # run apart from the tests that make some
        kept = admin.create_group("kept-over-restart")
        admin.assign_role("Viewer", group_id=kept["id"])
    assignments_before = admin.list_role_assignments()
    groups_before = admin.list_groups()
    assert assignments_before and groups_before

    master["proc"].terminate()
    master["proc"].wait(timeout=10)
    proc, session, port = start_master(
        master["tmp"], "--auth-required", "--rbac")
    # replace the fixture's handles so later tests (and teardown) see the
    # live master, not the one we just terminated
    master.update(proc=proc, session=session, port=port)
    session.login("admin")
    assert session.list_role_assignments() == assignments_before
    assert session.list_groups() == groups_before
    # enforcement still live for a re-logged-in unassigned user
    s = MasterSession("127.0.0.1", port, timeout=10, retries=2)
    s.login("nobody", "pw")
    with pytest.raises(MasterError) as err:
        s.create_experiment({"name": "x", "entrypoint": "x:Y"})
    assert err.value.status == 403


def test_assignments_inert_without_rbac_flag(master):
    """Role-granted ClusterAdmin must not unlock the admin surface when the
    master restarts without --rbac (assignments persist but are inert)."""
    admin = master["session"]
    eve = admin.create_user("eve", "pw")
    admin.assign_role("ClusterAdmin", user_id=eve["id"])

    master["proc"].terminate()
    master["proc"].wait(timeout=10)
    proc, session, port = start_master(master["tmp"], "--auth-required")
    master.update(proc=proc, session=session, port=port)

    s = MasterSession("127.0.0.1", port, timeout=10, retries=2)
    s.login("eve", "pw")
    assert s.my_permissions()["enforced"] is False
    with pytest.raises(MasterError) as err:
        s.create_user("eve-minion", "pw")
    assert err.value.status == 403
    # the real admin flag still works
    session.login("admin")
    assert session.create_user("by-admin", "pw")["username"] == "by-admin"
