// determined-clone-tpu agent — TPU-VM node daemon.
//
// C++ equivalent of the reference agent (agent/cmd/determined-agent,
// agent/internal/agent.go): detects TPU chips, registers with the master,
// heartbeats (HTTP long-poll replaces the reference websocket — same
// reconnect-with-backoff semantics, agent.go:330), launches task processes
// (process runner first; container runtimes are a later layer), forwards
// exit events and log batches.
//
// TPU detection (replaces nvidia-smi/rocm-smi parsing, detect/detect.go:19):
//   1. DCT_AGENT_SLOTS / DCT_AGENT_TOPOLOGY env (explicit + artificial slots
//      for tests — detect.go:39's trick)
//   2. /dev/accel* device files (TPU VM runtime)
//   3. fallback: 0 chips (cpu-only agent, zero-slot aux tasks)
#include <dirent.h>
#include <fcntl.h>
#include <signal.h>
#include <sys/prctl.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <sys/wait.h>
#include <unistd.h>

#include <chrono>
#include <cstring>
#include <fstream>
#include <iostream>
#include <iterator>
#include <map>
#include <sstream>
#include <string>
#include <thread>
#include <vector>

#include "../../master/src/config_file.h"
#include "../../master/src/http.h"
#include "../../master/src/json.h"
#include "docker.h"

namespace dct {
namespace {

struct AgentConfig {
  std::string master_host = "127.0.0.1";
  int master_port = 8080;
  std::string id;
  std::string resource_pool = "default";
  int slots = -1;           // -1 = autodetect
  std::string topology;
  double heartbeat_sec = 1.0;
  std::string work_dir = ".";
  // task runtime (≈ agent/internal/containers + pkg/docker):
  //   process   — fate-shared child (PDEATHSIG; dies with the agent)
  //   container — detached supervisor+task; survives agent restarts and is
  //               reattached from the state file (manager.go:76 semantics)
  //   docker    — container semantics with the task inside `docker run`
  std::string runtime = "process";
  std::string docker_image = "python:3.11-slim";
};

// One device node per TPU chip. Older generations show up as /dev/accelN;
// a v5e host exposes its chips through VFIO instead, one numbered group
// each (/dev/vfio/0 ... beside the /dev/vfio/vfio control node) and no
// /dev/accel* at all — seen on the v5e machine chip_smoke.py ran on.
std::vector<std::string> list_accel_devices() {
  std::vector<std::string> out;
  if (DIR* dev = ::opendir("/dev")) {
    while (dirent* entry = ::readdir(dev)) {
      if (std::strncmp(entry->d_name, "accel", 5) == 0) {
        out.push_back("/dev/" + std::string(entry->d_name));
      }
    }
    ::closedir(dev);
  }
  if (!out.empty()) return out;
  if (DIR* vfio = ::opendir("/dev/vfio")) {
    while (dirent* entry = ::readdir(vfio)) {
      const char* name = entry->d_name;
      if (*name != '\0' &&
          std::strspn(name, "0123456789") == std::strlen(name)) {
        out.push_back("/dev/vfio/" + std::string(name));
      }
    }
    ::closedir(vfio);
  }
  return out;
}

int detect_tpu_chips(std::string* topology) {
  if (const char* env = std::getenv("DCT_AGENT_SLOTS")) {
    if (const char* topo = std::getenv("DCT_AGENT_TOPOLOGY")) *topology = topo;
    return std::atoi(env);
  }
  int count = static_cast<int>(list_accel_devices().size());
  if (count > 0 && topology->empty()) {
    // named from what the agent can see: the device nodes say how many
    // chips, not which generation (DCT_AGENT_TOPOLOGY names that)
    *topology = "tpu-" + std::to_string(count);
  }
  return count;
}

struct RunningTask {
  pid_t pid = 0;          // direct child (process) or supervisor (container)
  pid_t task_pid = 0;     // the actual task process (container runtimes)
  std::string allocation_id;
  std::string log_path;
  bool preempt_sent = false;
  bool adopted = false;   // reattached after an agent restart: `pid` is not
                          // our child, so liveness is polled and the exit
                          // code comes from the supervisor's exit file
  int dead_polls = 0;     // adopted: polls since the task vanished (grace
                          // for the supervisor's exit-file write)
  std::string alloc_token;  // data-plane credential: log shipping must
                            // authenticate under --auth-required (kept
                            // last: positional inits predate the field)
};

bool pid_alive(pid_t pid) {
  return pid > 0 && (::kill(pid, 0) == 0 || errno == EPERM);
}

std::string read_proc_file(pid_t pid, const char* name) {
  std::ifstream in("/proc/" + std::to_string(pid) + "/" + name,
                   std::ios::binary);
  if (!in.good()) return "";
  return std::string((std::istreambuf_iterator<char>(in)),
                     std::istreambuf_iterator<char>());
}

bool has_nul_delimited(const std::string& data, const std::string& needle) {
  size_t pos = 0;
  while ((pos = data.find(needle, pos)) != std::string::npos) {
    // whole entry: preceded by NUL/start, followed by NUL/end
    bool start_ok = pos == 0 || data[pos - 1] == '\0';
    size_t end = pos + needle.size();
    bool end_ok = end == data.size() || data[end] == '\0';
    if (start_ok && end_ok) return true;
    ++pos;
  }
  return false;
}

// pid-reuse-proof identity for a task process. The exec'd task carries
// DCT_ALLOCATION_ID in /proc/<pid>/environ (environ reflects the exec-time
// environment, which setenv-before-exec populates — NOT post-fork setenv,
// so a never-exec'd supervisor cannot carry it). The docker runtime's task
// pid is the docker CLI, whose env has no task vars but whose cmdline
// names the container: --name dct-task-<alloc>.
bool proc_matches_task(pid_t pid, const std::string& alloc_id) {
  if (has_nul_delimited(read_proc_file(pid, "environ"),
                        "DCT_ALLOCATION_ID=" + alloc_id)) {
    return true;
  }
  return has_nul_delimited(read_proc_file(pid, "cmdline"),
                           "dct-task-" + alloc_id);
}

int b64_value(char c) {
  if (c >= 'A' && c <= 'Z') return c - 'A';
  if (c >= 'a' && c <= 'z') return c - 'a' + 26;
  if (c >= '0' && c <= '9') return c - '0' + 52;
  if (c == '+') return 62;
  if (c == '/') return 63;
  return -1;
}

std::string b64_decode(const std::string& in) {
  std::string out;
  int buf = 0, bits = 0;
  for (char c : in) {
    int v = b64_value(c);
    if (v < 0) continue;  // padding / whitespace
    buf = (buf << 6) | v;
    bits += 6;
    if (bits >= 8) {
      bits -= 8;
      out += static_cast<char>((buf >> bits) & 0xFF);
    }
  }
  return out;
}

void mkdirs_for(const std::string& file_path) {
  std::string cur;
  for (size_t i = 0; i < file_path.size(); ++i) {
    if (file_path[i] == '/' && !cur.empty()) ::mkdir(cur.c_str(), 0755);
    cur += file_path[i];
  }
}

class Agent {
 public:
  explicit Agent(AgentConfig config) : config_(std::move(config)) {}

  int run() {
    if (config_.id.empty()) {
      char host[256] = "agent";
      ::gethostname(host, sizeof(host));
      config_.id = std::string(host) + "-" + std::to_string(::getpid());
    }
    if (config_.slots < 0) {
      config_.slots = detect_tpu_chips(&config_.topology);
    }
    // absolute work dir: children chdir into per-task run dirs, so every
    // path derived from work_dir (task logs) must not be cwd-relative
    if (!config_.work_dir.empty() && config_.work_dir[0] != '/') {
      char cwd[4096];
      if (::getcwd(cwd, sizeof(cwd))) {
        config_.work_dir = std::string(cwd) + "/" + config_.work_dir;
      }
    }
    std::cerr << "[agent] id=" << config_.id << " slots=" << config_.slots
              << " topology=" << config_.topology
              << " runtime=" << config_.runtime << std::endl;

    // reattach-after-restart (container/docker runtimes): adopt surviving
    // tasks BEFORE the first heartbeat so the master never sees them absent
    if (config_.runtime != "process") reattach_tasks();

    // register with reconnect+backoff (≈ agent.go:246,330)
    int backoff_ms = 500;
    while (true) {
      if (register_with_master()) break;
      std::cerr << "[agent] master unreachable; retrying in "
                << backoff_ms << "ms" << std::endl;
      std::this_thread::sleep_for(std::chrono::milliseconds(backoff_ms));
      backoff_ms = std::min(backoff_ms * 2, 15000);
    }

    while (true) {
      reap_tasks();
      if (!heartbeat()) {
        // lost master: back off, re-register (reservations survive on the
        // master until its agent_timeout — the amnesia window)
        std::this_thread::sleep_for(std::chrono::seconds(1));
        register_with_master();
        continue;
      }
      std::this_thread::sleep_for(std::chrono::milliseconds(
          static_cast<int>(config_.heartbeat_sec * 1000)));
    }
  }

 private:
  bool register_with_master() {
    Json body = Json::object();
    char host[256] = "127.0.0.1";
    ::gethostname(host, sizeof(host));
    body.set("id", config_.id).set("slots", config_.slots)
        .set("topology", config_.topology)
        .set("resource_pool", config_.resource_pool)
        .set("address", std::string(host));
    auto resp = http_request(config_.master_host, config_.master_port, "POST",
                             "/api/v1/agents/register", body.dump(), 10);
    return resp && resp->status == 200;
  }

  bool heartbeat() {
    Json running = Json::array();
    for (const auto& [aid, task] : tasks_) running.push_back(aid);
    Json body = Json::object();
    body.set("running", running);
    // at-least-once exit reporting: a lost task_event POST must not leave
    // the master thinking the task still runs (it would re-issue a start);
    // exits ride every heartbeat until one succeeds, master side is
    // idempotent
    size_t exits_sent = pending_exits_.size();
    Json exited = Json::array();
    for (const auto& e : pending_exits_) exited.push_back(e);
    body.set("exited", exited);
    auto resp = http_request(
        config_.master_host, config_.master_port, "POST",
        "/api/v1/agents/" + config_.id + "/heartbeat", body.dump(), 10);
    if (!resp || resp->status != 200) return false;
    pending_exits_.erase(pending_exits_.begin(),
                         pending_exits_.begin() + exits_sent);
    Json j = Json::parse(resp->body);
    for (const auto& cmd : j["commands"].elements()) {
      const std::string& type = cmd["type"].as_string();
      if (type == "start") {
        start_task(cmd);
      } else if (type == "preempt") {
        preempt_task(cmd["allocation_id"].as_string());
      } else if (type == "kill") {
        kill_task(cmd["allocation_id"].as_string());
      }
    }
    return true;
  }

  // Materialize the experiment's model-def context directory for a trial
  // (≈ prep_container.py:29 --download_context_directory). Returns the run
  // dir to chdir into, or "" to inherit the agent's cwd.
  std::string prepare_context(const Json& cmd, const std::string& alloc_id) {
    if (!cmd.has("trial")) return "";
    int64_t exp_id = cmd["trial"]["experiment_id"].as_int();
    // authenticate with the allocation token: under --auth-required the
    // experiments root only opens reads to holders of a live alloc token
    std::map<std::string, std::string> headers;
    if (!cmd["alloc_token"].as_string().empty()) {
      headers["Authorization"] = "Bearer " + cmd["alloc_token"].as_string();
    }
    auto resp = http_request(
        config_.master_host, config_.master_port, "GET",
        "/api/v1/experiments/" + std::to_string(exp_id) + "/context", "", 30,
        headers);
    if (!resp || resp->status != 200) return "";
    Json ctx;
    try {
      ctx = Json::parse(resp->body);
    } catch (const std::exception&) {
      return "";
    }
    if (!ctx["context"].is_array() || ctx["context"].size() == 0) return "";
    std::string run_dir = config_.work_dir + "/run-" + alloc_id;
    ::mkdir(run_dir.c_str(), 0755);
    for (const auto& f : ctx["context"].elements()) {
      const std::string& rel = f["path"].as_string();
      if (rel.empty() || rel[0] == '/' ||
          rel.find("..") != std::string::npos) {
        continue;  // master validates too; belt-and-braces
      }
      std::string full = run_dir + "/" + rel;
      mkdirs_for(full);
      std::ofstream out(full, std::ios::binary);
      out << b64_decode(f["content_b64"].as_string());
    }
    return run_dir;
  }

  // The DCT_* environment one task sees (≈ container Entrypoint + DET_*
  // env, tasks/task.go:236). Shared by all runtimes: process/container
  // apply it via setenv before exec; docker turns it into -e flags.
  std::map<std::string, std::string> task_env(const Json& cmd,
                                              const std::string& alloc_id) {
    std::map<std::string, std::string> env;
    env["DCT_MASTER_HOST"] = config_.master_host;
    env["DCT_MASTER_PORT"] = std::to_string(config_.master_port);
    env["DCT_ALLOCATION_ID"] = alloc_id;
    // allocation-scoped credential: the task server requires it on every
    // request, and harness→master calls authenticate with it
    env["DCT_ALLOC_TOKEN"] = cmd["alloc_token"].as_string();
    env["DCT_AGENT_ID"] = config_.id;
    env["DCT_SLOTS"] = std::to_string(cmd["slots"].as_int());
    env["DCT_RANK"] = std::to_string(cmd["rank"].as_int());
    env["DCT_WORLD_SIZE"] = std::to_string(cmd["world_size"].as_int());
    env["DCT_N_SLICES"] = std::to_string(cmd["n_slices"].as_int(1));
    env["DCT_TASK_TYPE"] = cmd["task_type"].as_string();
    if (cmd.has("trial")) {
      env["DCT_TRIAL_ID"] = std::to_string(cmd["trial"]["id"].as_int());
      env["DCT_EXPERIMENT_ID"] =
          std::to_string(cmd["trial"]["experiment_id"].as_int());
      env["DCT_HPARAMS"] = cmd["trial"]["hparams"].dump();
      env["DCT_TARGET_UNITS"] =
          std::to_string(cmd["trial"]["target_units"].as_int());
      env["DCT_LATEST_CHECKPOINT"] =
          cmd["trial"]["latest_checkpoint"].as_string();
      env["DCT_EXPERIMENT_CONFIG"] = cmd["config"].dump();
    }
    if (cmd["spec"]["env"].is_object()) {
      for (const auto& [k, v] : cmd["spec"]["env"].items()) {
        env[k] = v.as_string();
      }
    }
    return env;
  }

  // The in-container / in-process command for one task: NTSC argv, or the
  // trial-harness invocation.
  std::vector<std::string> task_argv(const Json& cmd) {
    const Json& argv = cmd["spec"]["argv"];
    std::vector<std::string> out;
    if (argv.is_array() && argv.size() > 0) {
      for (const auto& e : argv.elements()) out.push_back(e.as_string());
      return out;
    }
    const std::string entrypoint = cmd["spec"]["entrypoint"].as_string();
    if (!entrypoint.empty()) {
      out = {"python", "-m", "determined_clone_tpu.exec.trial", entrypoint};
    }
    return out;
  }

  // Child-side: apply env, chdir, redirect stdout/stderr to the log, exec.
  // Never returns.
  [[noreturn]] void exec_task_child(const Json& cmd,
                                    const std::string& alloc_id,
                                    const std::string& log_path,
                                    const std::string& run_dir) {
    for (const auto& [k, v] : task_env(cmd, alloc_id)) {
      ::setenv(k.c_str(), v.c_str(), 1);
    }
    // task cwd is the run dir (uploaded context) or the agent work dir —
    // never the agent's own cwd (trials import model code from cwd)
    const std::string& task_cwd = run_dir.empty() ? config_.work_dir : run_dir;
    if (::chdir(task_cwd.c_str()) != 0) {
      std::cerr << "chdir " << task_cwd << " failed" << std::endl;
      std::_Exit(82);
    }
    // stdout/stderr → log file (shipped to master on exit; live shipping
    // is the harness's log-batch POST)
    FILE* log = ::freopen(log_path.c_str(), "a", stdout);
    (void)log;
    ::dup2(::fileno(stdout), ::fileno(stderr));

    std::vector<std::string> args = task_argv(cmd);
    if (args.empty()) {
      std::cerr << "no argv/entrypoint for " << alloc_id << std::endl;
      std::_Exit(80);
    }
    std::vector<char*> cargs;
    for (auto& a : args) cargs.push_back(a.data());
    cargs.push_back(nullptr);
    ::execvp(cargs[0], cargs.data());
    std::cerr << "execvp failed: " << std::strerror(errno) << std::endl;
    std::_Exit(81);
  }

  std::string exit_file(const std::string& alloc_id) const {
    return config_.work_dir + "/task-" + alloc_id + ".exit";
  }
  std::string state_file() const {
    return config_.work_dir + "/agent-state.json";
  }

  // Detached supervisor+task pair: the supervisor (a new session, so it
  // survives the agent dying by any signal) waits for the task, records the
  // exit code to a file — readable after a reattach, when waitpid is
  // impossible — and exits with the same code for the normal path.
  void start_detached(const Json& cmd, const std::string& alloc_id,
                      const std::string& log_path, const std::string& run_dir,
                      bool docker) {
    int pipefd[2];
    if (::pipe(pipefd) != 0) return;
    ::unlink(exit_file(alloc_id).c_str());
    pid_t sup = ::fork();
    if (sup == 0) {
      ::setsid();  // detach: agent death must not take the task down
      ::close(pipefd[0]);
      pid_t task = ::fork();
      if (task == 0) {
        ::close(pipefd[1]);
        if (docker) {
          auto env = task_env(cmd, alloc_id);
          const std::string cwd = run_dir.empty() ? config_.work_dir : run_dir;
          auto argv = docker_run_argv(alloc_id, config_.docker_image,
                                      config_.work_dir, cwd, env,
                                      list_accel_devices(), task_argv(cmd));
          FILE* log = ::freopen(log_path.c_str(), "a", stdout);
          (void)log;
          ::dup2(::fileno(stdout), ::fileno(stderr));
          std::vector<char*> cargs;
          for (auto& a : argv) cargs.push_back(a.data());
          cargs.push_back(nullptr);
          ::execvp(cargs[0], cargs.data());
          std::_Exit(81);
        }
        exec_task_child(cmd, alloc_id, log_path, run_dir);
      }
      // supervisor: report the task pid, wait, persist the exit code
      ::write(pipefd[1], &task, sizeof(task));
      ::close(pipefd[1]);
      int code = 80;  // fork failure: the task never ran
      if (task > 0) {
        int status = 0;
        ::waitpid(task, &status, 0);
        code = WIFEXITED(status) ? WEXITSTATUS(status)
                                 : 128 + WTERMSIG(status);
      }
      {
        std::ofstream out(exit_file(alloc_id) + ".tmp");
        out << code;
      }
      ::rename((exit_file(alloc_id) + ".tmp").c_str(),
               exit_file(alloc_id).c_str());
      std::_Exit(code & 0xFF);
    }
    ::close(pipefd[1]);
    pid_t task_pid = 0;
    ssize_t n = ::read(pipefd[0], &task_pid, sizeof(task_pid));
    (void)n;
    ::close(pipefd[0]);
    if (sup > 0) {
      tasks_[alloc_id] = RunningTask{sup, task_pid, alloc_id, log_path,
                                     false, false, 0, ""};
      tasks_[alloc_id].alloc_token = cmd["alloc_token"].as_string();
      persist_state();
      send_event(alloc_id, "running", 0, "");
      std::cerr << "[agent] started " << alloc_id << " supervisor=" << sup
                << " task=" << task_pid
                << (docker ? " (docker)" : " (container)") << std::endl;
    }
  }

  void start_task(const Json& cmd) {
    const std::string& alloc_id = cmd["allocation_id"].as_string();
    if (tasks_.count(alloc_id)) return;  // duplicate start

    std::string log_path =
        config_.work_dir + "/task-" + alloc_id + ".log";
    std::string run_dir = prepare_context(cmd, alloc_id);
    if (config_.runtime == "container" || config_.runtime == "docker") {
      start_detached(cmd, alloc_id, log_path, run_dir,
                     config_.runtime == "docker");
      return;
    }
    pid_t pid = ::fork();
    if (pid == 0) {
      // fate-sharing: if the agent dies (even SIGKILL), its tasks must not
      // become orphans (≈ pid_server/pid_client, harness ipc.py:264-553)
      ::prctl(PR_SET_PDEATHSIG, SIGKILL);
      if (::getppid() == 1) std::_Exit(83);  // agent died before prctl
      exec_task_child(cmd, alloc_id, log_path, run_dir);
    }
    if (pid > 0) {
      tasks_[alloc_id] = RunningTask{pid, 0, alloc_id, log_path, false, false, 0, ""};
      tasks_[alloc_id].alloc_token = cmd["alloc_token"].as_string();
      send_event(alloc_id, "running", 0, "");
      std::cerr << "[agent] started " << alloc_id << " pid=" << pid << std::endl;
    }
  }

  void preempt_task(const std::string& alloc_id) {
    auto it = tasks_.find(alloc_id);
    if (it == tasks_.end() || it->second.preempt_sent) return;
    // cooperative: harness polls the preempt endpoint; SIGTERM is the
    // belt-and-braces (exec/launch.py:18's SLURM SIGTERM semantics).
    // Signal the task, not the supervisor (which must survive to record
    // the exit code). task_pid <= 0 (supervisor fork failure) must never
    // reach kill() — kill(-1, sig) signals everything we can.
    pid_t target = it->second.task_pid > 0 ? it->second.task_pid
                                           : it->second.pid;
    if (target > 0) ::kill(target, SIGTERM);
    it->second.preempt_sent = true;
  }

  void kill_task(const std::string& alloc_id) {
    auto it = tasks_.find(alloc_id);
    if (it == tasks_.end()) return;
    if (config_.runtime == "docker") {
      // the docker CLI process does not forward SIGKILL to the container;
      // double-fork so the helper can't accumulate as a zombie
      std::string name = "dct-task-" + alloc_id;
      pid_t helper = ::fork();
      if (helper == 0) {
        if (::fork() == 0) {
          ::execlp("docker", "docker", "kill", name.c_str(), nullptr);
          std::_Exit(127);
        }
        std::_Exit(0);
      }
      if (helper > 0) ::waitpid(helper, nullptr, 0);
    }
    pid_t target = it->second.task_pid > 0 ? it->second.task_pid
                                           : it->second.pid;
    if (target > 0) ::kill(target, SIGKILL);
  }

  // Reattach after an agent restart (≈ containers/manager.go:76): re-adopt
  // tasks from the state file whose processes still run; report exits for
  // those that finished while the agent was down.
  void reattach_tasks() {
    std::ifstream in(state_file());
    if (!in.good()) return;
    Json state;
    try {
      std::stringstream buf;
      buf << in.rdbuf();
      state = Json::parse(buf.str());
    } catch (const std::exception&) {
      return;
    }
    for (const auto& t : state["tasks"].elements()) {
      const std::string alloc_id = t["allocation_id"].as_string();
      pid_t sup = static_cast<pid_t>(t["supervisor_pid"].as_int());
      pid_t task = static_cast<pid_t>(t["task_pid"].as_int());
      // identity check beats pid reuse (env for exec'd tasks, container
      // name in cmdline for the docker CLI)
      bool alive = pid_alive(task) && proc_matches_task(task, alloc_id);
      if (alive) {
        tasks_[alloc_id] = RunningTask{sup, task, alloc_id,
                                       t["log_path"].as_string(), false,
                                       true, 0, ""};
        tasks_[alloc_id].alloc_token = t["alloc_token"].as_string();
        if (tasks_[alloc_id].alloc_token.empty()) {
          // pre-upgrade state file: under --auth-required the master will
          // 401 this task's log batches — say so rather than losing them
          std::cerr << "[agent] WARNING: reattached " << alloc_id
                    << " without an alloc token (pre-upgrade state file); "
                    << "log shipping will fail if the master requires auth"
                    << std::endl;
        }
        std::cerr << "[agent] reattached " << alloc_id << " task=" << task
                  << std::endl;
        continue;
      }
      // finished (or lost) while we were down: the supervisor's exit file
      // has the code; without it the outcome is unknown -> error
      int exit_code = 1;
      std::string error = "task lost across agent restart";
      std::ifstream ef(exit_file(alloc_id));
      if (ef.good()) {
        ef >> exit_code;
        error = exit_code ? "task failed" : "";
      }
      RunningTask lost{0, 0, alloc_id, t["log_path"].as_string(),
                       false, false, 0, ""};
      lost.alloc_token = t["alloc_token"].as_string();
      ship_logs(lost);
      Json rec = Json::object();
      rec.set("allocation_id", alloc_id).set("exit_code", exit_code)
          .set("error", error);
      pending_exits_.push_back(std::move(rec));
      std::cerr << "[agent] task " << alloc_id
                << " finished while agent was down: exit " << exit_code
                << std::endl;
    }
    persist_state();
  }

  void persist_state() {
    if (config_.runtime == "process") return;  // fate-shared: nothing survives
    Json tasks = Json::array();
    for (const auto& [aid, t] : tasks_) {
      Json j = Json::object();
      j.set("allocation_id", aid)
          .set("supervisor_pid", static_cast<int64_t>(t.pid))
          .set("task_pid", static_cast<int64_t>(t.task_pid))
          .set("log_path", t.log_path)
          // needed so a reattached task's logs can still authenticate;
          // the file is 0600 below — it now holds live credentials
          .set("alloc_token", t.alloc_token);
      tasks.push_back(j);
    }
    Json state = Json::object();
    state.set("tasks", tasks);
    // owner-only from the first byte: the state file carries alloc tokens,
    // which on a multi-user host must not be readable by other accounts
    std::string tmp = state_file() + ".tmp";
    int fd = ::open(tmp.c_str(), O_WRONLY | O_CREAT | O_TRUNC, 0600);
    if (fd < 0) return;
    std::string data = state.dump();
    ssize_t off = 0;
    while (off < static_cast<ssize_t>(data.size())) {
      ssize_t n = ::write(fd, data.data() + off, data.size() - off);
      if (n <= 0) break;
      off += n;
    }
    ::close(fd);
    ::rename(tmp.c_str(), state_file().c_str());
  }

  void finish_task(const std::string& alloc_id, const RunningTask& task,
                   int exit_code) {
    ship_logs(task);
    // fast path now; the heartbeat carries it again until acked
    send_event(alloc_id, "exited", exit_code,
               exit_code ? "task failed" : "");
    Json rec = Json::object();
    rec.set("allocation_id", alloc_id).set("exit_code", exit_code)
        .set("error", exit_code ? "task failed" : "");
    pending_exits_.push_back(std::move(rec));
    std::cerr << "[agent] task " << alloc_id << " exited " << exit_code
              << std::endl;
  }

  void reap_tasks() {
    bool changed = false;
    for (auto it = tasks_.begin(); it != tasks_.end();) {
      const RunningTask& task = it->second;
      if (task.adopted) {
        // not our child: poll the TASK's liveness with the identity check
        // (a bare kill(pid, 0) would follow a reused pid forever)
        if (pid_alive(task.task_pid) &&
            proc_matches_task(task.task_pid, it->first)) {
          it->second.dead_polls = 0;
          ++it;
          continue;
        }
        // task gone: the supervisor writes the exit file just before it
        // exits — give it a grace window before assuming a crash
        std::ifstream ef(exit_file(it->first));
        if (!ef.good() && ++it->second.dead_polls < 20) {
          ++it;
          continue;
        }
        int exit_code = 1;
        if (ef.good()) ef >> exit_code;
        finish_task(it->first, task, exit_code);
        it = tasks_.erase(it);
        changed = true;
        continue;
      }
      int status = 0;
      pid_t done = ::waitpid(task.pid, &status, WNOHANG);
      if (done == task.pid) {
        // process runtime: the child's status; container/docker: the
        // supervisor exits with the task's code
        int exit_code = WIFEXITED(status) ? WEXITSTATUS(status)
                                          : 128 + WTERMSIG(status);
        finish_task(it->first, task, exit_code);
        it = tasks_.erase(it);
        changed = true;
      } else {
        ++it;
      }
    }
    if (changed) persist_state();
  }

  void ship_logs(const RunningTask& task) {
    std::ifstream in(task.log_path);
    if (!in.good()) return;
    Json logs = Json::array();
    std::string line;
    int count = 0;
    while (std::getline(in, line) && count < 5000) {
      logs.push_back(line);
      ++count;
    }
    Json body = Json::object();
    body.set("logs", logs);
    std::map<std::string, std::string> headers;
    if (!task.alloc_token.empty()) {
      headers["Authorization"] = "Bearer " + task.alloc_token;
    }
    http_request(config_.master_host, config_.master_port, "POST",
                 "/api/v1/allocations/" + task.allocation_id + "/logs",
                 body.dump(), 10, headers);
  }

  void send_event(const std::string& alloc_id, const std::string& event,
                  int exit_code, const std::string& error) {
    Json body = Json::object();
    body.set("allocation_id", alloc_id).set("event", event)
        .set("exit_code", exit_code).set("error", error);
    http_request(config_.master_host, config_.master_port, "POST",
                 "/api/v1/agents/" + config_.id + "/task_event", body.dump(),
                 10);
  }

  AgentConfig config_;
  std::map<std::string, RunningTask> tasks_;
  std::vector<Json> pending_exits_;  // unacked exit reports
};

}  // namespace
}  // namespace dct

namespace {
// agent config file (≈ agent.yaml via viper, options.go:47); the parser is
// shared with the master (config_file.h) so the format cannot drift
int apply_agent_config_file(const std::string& path,
                            dct::AgentConfig* config) {
  std::map<std::string, std::string> values;
  try {
    values = dct::configfile::parse(path);
  } catch (const std::exception& e) {
    std::cerr << e.what() << "\n";
    return 2;
  }
  for (const auto& [key, value] : values) {
    if (key == "master_host") config->master_host = value;
    else if (key == "master_port") config->master_port = std::atoi(value.c_str());
    else if (key == "id") config->id = value;
    else if (key == "resource_pool") config->resource_pool = value;
    else if (key == "slots") config->slots = std::atoi(value.c_str());
    else if (key == "topology") config->topology = value;
    else if (key == "work_dir") config->work_dir = value;
    else if (key == "runtime") config->runtime = value;
    else if (key == "docker_image") config->docker_image = value;
    else {
      std::cerr << "unknown config key '" << key << "' in " << path << "\n";
      return 2;
    }
  }
  return 0;
}
}  // namespace

int main(int argc, char** argv) {
  dct::AgentConfig config;
  for (int i = 1; i < argc; ++i) {
    if (!std::strcmp(argv[i], "--config") && i + 1 < argc) {
      int rc = apply_agent_config_file(argv[i + 1], &config);
      if (rc) return rc;
    }
  }
  for (int i = 1; i < argc; ++i) {
    if (!std::strcmp(argv[i], "--config") && i + 1 < argc) {
      ++i;  // applied above; flags override
    } else if (!std::strcmp(argv[i], "--master-host") && i + 1 < argc) {
      config.master_host = argv[++i];
    } else if (!std::strcmp(argv[i], "--master-port") && i + 1 < argc) {
      config.master_port = std::atoi(argv[++i]);
    } else if (!std::strcmp(argv[i], "--id") && i + 1 < argc) {
      config.id = argv[++i];
    } else if (!std::strcmp(argv[i], "--resource-pool") && i + 1 < argc) {
      config.resource_pool = argv[++i];
    } else if (!std::strcmp(argv[i], "--slots") && i + 1 < argc) {
      config.slots = std::atoi(argv[++i]);
    } else if (!std::strcmp(argv[i], "--topology") && i + 1 < argc) {
      config.topology = argv[++i];
    } else if (!std::strcmp(argv[i], "--work-dir") && i + 1 < argc) {
      config.work_dir = argv[++i];
    } else if (!std::strcmp(argv[i], "--runtime") && i + 1 < argc) {
      config.runtime = argv[++i];
      if (config.runtime != "process" && config.runtime != "container" &&
          config.runtime != "docker") {
        std::cerr << "unknown runtime '" << config.runtime
                  << "' (process|container|docker)\n";
        return 2;
      }
    } else if (!std::strcmp(argv[i], "--docker-image") && i + 1 < argc) {
      config.docker_image = argv[++i];
    } else if (!std::strcmp(argv[i], "--help")) {
      std::cout << "usage: dct-agent [--config FILE] "
                   "[--master-host H] [--master-port P] "
                   "[--id ID] [--resource-pool POOL] [--slots N] "
                   "[--topology T] [--work-dir DIR] "
                   "[--runtime process|container|docker] "
                   "[--docker-image IMG]\n";
      return 0;
    }
  }
  dct::Agent agent(config);
  return agent.run();
}
