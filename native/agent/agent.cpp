// dtpu-agent: per-host daemon that runs trial processes.
//
// Native equivalent of the reference's Go agent (agent/internal/agent.go):
// registers its slots with the master, long-polls for work, launches trial
// processes with the platform env, ships their stdout/stderr to the master
// task-log API, and reports exits.  Differences from the reference are
// deliberate TPU redesigns:
//   - slots are TPU chips (or artificial slots via --slots for tests /
//     CPU hosts), not nvidia-smi GPUs;
//   - transport is HTTP long-poll against the master REST API instead of a
//     bespoke websocket protocol (one port, one protocol end to end);
//   - processes are plain fork/exec of the harness (TPU VMs run training
//     directly on the host), not Docker containers.

#include <fcntl.h>
#include <signal.h>
#include <sys/types.h>
#include <sys/wait.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cctype>
#include <cstdlib>
#include <cstring>
#include <filesystem>
#include <fstream>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include "../common/http.hpp"
#include "../common/json.hpp"

namespace dtpu {

struct Options {
  std::string master_host = "127.0.0.1";
  int master_port = 8080;
  std::string id = "agent-1";
  std::string advertised_host = "127.0.0.1";
  std::string pool = "default";
  int slots = 1;
  std::string slot_type = "cpu";  // tpu when detect_slots() finds chips
  // Topology label: agents sharing a slice_id are ICI-reachable; crossing
  // labels means DCN.  On real TPU VMs this is the multislice slice name
  // (MEGASCALE_SLICE_ID); empty = unlabeled, master falls back to
  // one-host-per-slice placement.
  std::string slice_id;
  std::string python = "python";
  std::string user = "determined";
  std::string password;
  // pid files for running allocations live here so a restarted agent can
  // clean up orphaned process groups (reference: ReattachContainers,
  // agent/internal/agent.go:153 — our unit of recovery is kill+master
  // reschedule, since jax.distributed jobs restart whole-gang anyway)
  std::string state_dir;
  // TLS to the master: --master-cert names the CA bundle (typically the
  // master's own self-signed cert) that its chain must verify against
  // (reference harness/.../certs.py trust model)
  bool master_tls = false;
  std::string master_cert;
};

class Agent {
 public:
  explicit Agent(Options opts) : opts_(std::move(opts)) {}

  int run() {
    if (opts_.state_dir.empty()) {
      opts_.state_dir = "/tmp/dtpu-agent-" + opts_.id;
    }
    std::error_code ec;
    std::filesystem::create_directories(opts_.state_dir, ec);
    kill_orphans();
    if (!login() || !register_agent()) {
      fprintf(stderr, "agent %s: cannot reach master\n", opts_.id.c_str());
      return 1;
    }
    printf("dtpu-agent %s registered (%d slots)\n", opts_.id.c_str(), opts_.slots);
    fflush(stdout);
    while (true) {
      auto resp = master_req("GET",
                             "/api/v1/agents/" + opts_.id + "/work?timeout_seconds=30",
                             "", 45);
      if (!resp.ok()) {
        // master gone or restarting: re-login + re-register with backoff
        std::this_thread::sleep_for(std::chrono::seconds(2));
        login();
        register_agent();
        continue;
      }
      Json work;
      if (!Json::try_parse(resp.body, &work) || !work.is_array()) continue;
      for (const auto& item : work.elements()) {
        const std::string& type = item["type"].as_string();
        if (type == "launch") {
          launch(item);
        } else if (type == "kill") {
          kill_allocation(item["allocation_id"].as_string());
        } else if (type == "launch_task") {
          launch_task(item);
        } else if (type == "kill_task") {
          kill_allocation(item["task_id"].as_string());
        } else if (type == "gc") {
          run_gc(item);
        }
      }
    }
  }

 private:
  // authenticated request to the master; the token is refreshed by the
  // re-login path in run() when the master restarts with fresh state
  ClientResponse master_req(const std::string& method, const std::string& target,
                            const std::string& body = "", int timeout_sec = 10) {
    std::string tok;
    {
      std::lock_guard<std::mutex> lk(mu_);
      tok = token_;
    }
    return http_request(opts_.master_host, opts_.master_port, method, target, body,
                        timeout_sec, {{"Authorization", "Bearer " + tok}},
                        opts_.master_tls, opts_.master_cert);
  }

  bool login() {
    Json body = Json::object();
    body.set("username", opts_.user);
    body.set("password", opts_.password);
    auto resp = http_request(opts_.master_host, opts_.master_port, "POST",
                             "/api/v1/auth/login", body.dump(), 10, {},
                             opts_.master_tls, opts_.master_cert);
    if (!resp.ok()) return false;
    Json out;
    if (!Json::try_parse(resp.body, &out)) return false;
    std::lock_guard<std::mutex> lk(mu_);
    token_ = out["token"].as_string();
    return !token_.empty();
  }

  bool register_agent() {
    Json body = Json::object();
    body.set("id", opts_.id);
    body.set("host", opts_.advertised_host);
    body.set("pool", opts_.pool);
    body.set("slots", Json(opts_.slots));
    body.set("slot_type", opts_.slot_type);
    if (!opts_.slice_id.empty()) body.set("slice_id", opts_.slice_id);
    // Re-attach handshake (master crash-safe restart): report the
    // allocations whose processes are STILL running under this agent.  A
    // restarted master matches these against its journaled placements and
    // re-adopts the gang in place; allocations it cannot match come back
    // as kill work (stale processes from before a reschedule).
    // id only: the master takes trial ids and per-agent slot counts from
    // its own journaled groups, never from the report (an agent cannot
    // know the gang-wide layout, and a self-reported view could not be
    // trusted across restarts anyway)
    Json allocs = Json::array();
    {
      std::lock_guard<std::mutex> lk(mu_);
      for (const auto& [alloc_id, proc] : running_) {
        if (proc.trial_id < 0) continue;  // aux tasks are ephemeral by design
        allocs.push_back(Json::object().set("id", alloc_id));
      }
    }
    body.set("allocations", allocs);
    auto resp = master_req("POST", "/api/v1/agents", body.dump(), 10);
    return resp.ok();
  }

  std::string pidfile(const std::string& alloc_id) const {
    return opts_.state_dir + "/" + alloc_id + ".pid";
  }

  // A previous incarnation of this agent may have left trial process
  // groups running (they survive the agent's death as orphans, keep the
  // TPU chips busy, and post stale metrics).  On startup, SIGKILL every
  // process group recorded in the state dir that is still a run_trial
  // process; the master has already (or will) fail those allocations.
  void kill_orphans() {
    std::error_code ec;
    for (const auto& entry :
         std::filesystem::directory_iterator(opts_.state_dir, ec)) {
      if (ec) break;
      if (entry.path().extension() != ".pid") continue;
      std::ifstream in(entry.path());
      pid_t pid = 0;
      in >> pid;
      if (pid > 1) {
        // pid-reuse guard: only kill if it's still a run_trial process
        std::ifstream cmd("/proc/" + std::to_string(pid) + "/cmdline");
        std::string cmdline((std::istreambuf_iterator<char>(cmd)),
                            std::istreambuf_iterator<char>());
        if (cmdline.find("determined_tpu") != std::string::npos) {
          fprintf(stderr, "agent %s: killing orphaned trial pgid %d\n",
                  opts_.id.c_str(), pid);
          ::kill(-pid, SIGKILL);
        }
      }
      std::filesystem::remove(entry.path(), ec);
    }
  }

  // checkpoint-GC task: delete storage contents through the harness
  // StorageManager (reference exec/gc_checkpoints.py run as a task)
  void run_gc(const Json& work) {
    pid_t pid = fork();
    if (pid == 0) {
      setpgid(0, 0);
      setenv("DTPU_GC_SPEC", work.dump().c_str(), 1);
      execlp(opts_.python.c_str(), opts_.python.c_str(), "-m",
             "determined_tpu.exec.gc_checkpoints", (char*)nullptr);
      _exit(127);
    }
    if (pid > 0) {
      std::thread([pid] {
        int status = 0;
        waitpid(pid, &status, 0);
      }).detach();
    }
  }

  // Launch failed before the trial process existed (pipe() or fork()
  // EMFILE/EAGAIN/ENOMEM): tell the master the launch died so the
  // trial/task — and, for gangs, every OTHER rank's process via the
  // master's gang teardown — is failed instead of sitting RUNNING
  // forever.  A log line ships first so the trial log explains WHY this
  // rank never produced output.
  void report_launch_failure(int64_t trial_id, const std::string& alloc_id,
                             const std::string& task_id, const char* what) {
    fprintf(stderr, "agent %s: %s failed for %s\n", opts_.id.c_str(), what,
            (task_id.empty() ? alloc_id : task_id).c_str());
    Json log = Json::object();
    if (task_id.empty()) {
      log.set("trial_id", Json(trial_id));
    } else {
      log.set("task_id", task_id);
    }
    log.set("agent", opts_.id);
    Json lines = Json::array();
    lines.push_back("agent " + opts_.id + ": " + what +
                    " failed launching the trial process (allocation " +
                    (task_id.empty() ? alloc_id : task_id) + ")");
    log.set("lines", lines);
    master_req("POST", "/api/v1/logs", log.dump(), 10);
    if (!task_id.empty()) {
      Json tbody = Json::object();
      tbody.set("exit_code", Json(126));
      tbody.set("detail", std::string(what) + " failed launching the task process");
      master_req("POST", "/api/v1/tasks/" + task_id + "/exit", tbody.dump(), 10);
      return;
    }
    Json body = Json::object();
    body.set("exit_code", Json(126));
    body.set("allocation_id", alloc_id);
    master_req("POST", "/api/v1/trials/" + std::to_string(trial_id) + "/exit",
               body.dump(), 10);
  }

  void report_fork_failure(int64_t trial_id, const std::string& alloc_id,
                           const std::string& task_id, int out_pipe[2]) {
    close(out_pipe[0]);
    close(out_pipe[1]);
    report_launch_failure(trial_id, alloc_id, task_id, "fork");
  }

  void launch(const Json& work) {
    int64_t trial_id = work["trial_id"].as_int();
    const std::string alloc_id = work["allocation_id"].as_string();
    int out_pipe[2];
    if (pipe(out_pipe) != 0) {
      // fd exhaustion: a silent return here would leave THIS rank's
      // allocation RUNNING forever while its gang peers block in
      // rendezvous — same terminal report as a fork failure
      report_launch_failure(trial_id, alloc_id, "", "pipe");
      return;
    }

    pid_t pid = fork();
    if (pid < 0) {
      report_fork_failure(trial_id, alloc_id, "", out_pipe);
      return;
    }
    if (pid == 0) {
      // child: own process group so kill() reaches workers too
      setpgid(0, 0);
      dup2(out_pipe[1], STDOUT_FILENO);
      dup2(out_pipe[1], STDERR_FILENO);
      close(out_pipe[0]);
      close(out_pipe[1]);
      // platform env
      setenv("DTPU_MASTER_URL",
             ((opts_.master_tls ? "https://" : "http://") + opts_.master_host +
              ":" + std::to_string(opts_.master_port)).c_str(), 1);
      if (!opts_.master_cert.empty()) {
        setenv("DTPU_MASTER_CERT", opts_.master_cert.c_str(), 1);
      }
      setenv("DTPU_AGENT_ID", opts_.id.c_str(), 1);
      for (const auto& [k, v] : work["env"].items()) {
        setenv(k.c_str(), v.as_string().c_str(), 1);
      }
      std::string entry = work["entrypoint"].as_string();
      execlp(opts_.python.c_str(), opts_.python.c_str(), "-m",
             "determined_tpu.exec.run_trial", entry.c_str(), (char*)nullptr);
      _exit(127);
    }
    close(out_pipe[1]);
    {
      std::lock_guard<std::mutex> lk(mu_);
      RunningProc proc;
      proc.pid = pid;
      proc.trial_id = trial_id;
      running_[alloc_id] = proc;
    }
    {
      std::ofstream pf(pidfile(alloc_id), std::ios::trunc);
      pf << pid << "\n";
    }
    // reader thread: ship logs, then wait + report exit
    std::thread([this, pid, trial_id, alloc_id, fd = out_pipe[0]] {
      ship_logs_and_wait(fd, pid, trial_id, alloc_id);
    }).detach();
  }

  // generic aux task (NTSC analog): fork the given harness module with the
  // task env; logs ship to the master's task log file, exit reported to
  // the tasks API.  Tracked in running_ under the task id so kill_task
  // reuses the allocation kill path.
  void launch_task(const Json& work) {
    const std::string task_id = work["task_id"].as_string();
    int out_pipe[2];
    if (pipe(out_pipe) != 0) {
      report_launch_failure(0, "", task_id, "pipe");
      return;
    }
    pid_t pid = fork();
    if (pid < 0) {
      report_fork_failure(0, "", task_id, out_pipe);
      return;
    }
    if (pid == 0) {
      setpgid(0, 0);
      dup2(out_pipe[1], STDOUT_FILENO);
      dup2(out_pipe[1], STDERR_FILENO);
      close(out_pipe[0]);
      close(out_pipe[1]);
      setenv("DTPU_MASTER_URL",
             ((opts_.master_tls ? "https://" : "http://") + opts_.master_host +
              ":" + std::to_string(opts_.master_port)).c_str(), 1);
      if (!opts_.master_cert.empty()) {
        setenv("DTPU_MASTER_CERT", opts_.master_cert.c_str(), 1);
      }
      setenv("DTPU_AGENT_ID", opts_.id.c_str(), 1);
      for (const auto& [k, v] : work["env"].items()) {
        setenv(k.c_str(), v.as_string().c_str(), 1);
      }
      std::string module = work["module"].as_string();
      execlp(opts_.python.c_str(), opts_.python.c_str(), "-m", module.c_str(),
             (char*)nullptr);
      _exit(127);
    }
    close(out_pipe[1]);
    {
      std::lock_guard<std::mutex> lk(mu_);
      RunningProc proc;
      proc.pid = pid;
      running_[task_id] = proc;
    }
    {
      std::ofstream pf(pidfile(task_id), std::ios::trunc);
      pf << pid << "\n";
    }
    std::thread([this, pid, task_id, fd = out_pipe[0]] {
      ship_logs_and_wait(fd, pid, /*trial_id=*/-1, task_id, task_id);
    }).detach();
  }

  void ship_logs_and_wait(int fd, pid_t pid, int64_t trial_id,
                          const std::string& alloc_id,
                          const std::string& task_id = "") {
    std::string partial;
    std::vector<std::string> batch;
    char buf[8192];
    auto flush = [&]() {
      if (batch.empty()) return;
      Json body = Json::object();
      if (task_id.empty()) {
        body.set("trial_id", Json(trial_id));
      } else {
        body.set("task_id", task_id);
      }
      body.set("agent", opts_.id);  // log-pattern exclude_node attribution
      Json lines = Json::array();
      for (auto& l : batch) lines.push_back(l);
      body.set("lines", lines);
      master_req("POST", "/api/v1/logs", body.dump(), 10);
      batch.clear();
    };
    ssize_t n;
    while ((n = read(fd, buf, sizeof(buf))) > 0) {
      partial.append(buf, static_cast<size_t>(n));
      size_t pos;
      while ((pos = partial.find('\n')) != std::string::npos) {
        batch.push_back(partial.substr(0, pos));
        partial.erase(0, pos + 1);
        if (batch.size() >= 64) flush();
      }
      flush();
    }
    if (!partial.empty()) batch.push_back(partial);
    flush();
    close(fd);

    int status = 0;
    waitpid(pid, &status, 0);
    int exit_code = WIFEXITED(status) ? WEXITSTATUS(status)
                                      : 128 + (WIFSIGNALED(status) ? WTERMSIG(status) : 1);
    {
      std::lock_guard<std::mutex> lk(mu_);
      running_.erase(alloc_id);
    }
    {
      std::error_code ec;
      std::filesystem::remove(pidfile(alloc_id), ec);
    }
    if (!task_id.empty()) {
      // exit code distinguishes orderly drains (0/75) from crashes for the
      // master's fleet supervisor
      Json tbody = Json::object();
      tbody.set("exit_code", Json(exit_code));
      master_req("POST", "/api/v1/tasks/" + task_id + "/exit", tbody.dump(), 10);
      return;
    }
    Json body = Json::object();
    body.set("exit_code", Json(exit_code));
    body.set("allocation_id", alloc_id);
    master_req("POST", "/api/v1/trials/" + std::to_string(trial_id) + "/exit",
               body.dump(), 10);
  }

  void kill_allocation(const std::string& alloc_id) {
    pid_t pid = 0;
    {
      std::lock_guard<std::mutex> lk(mu_);
      auto it = running_.find(alloc_id);
      if (it == running_.end()) return;
      pid = it->second.pid;
    }
    // graceful SIGTERM (harness checkpoints on it), SIGKILL after grace
    ::kill(-pid, SIGTERM);
    std::thread([this, alloc_id, pid] {
      std::this_thread::sleep_for(std::chrono::seconds(15));
      // only escalate if this exact allocation/pid is still running; the pid
      // may have been reaped (and even reused by the OS) during the grace
      // period, in which case SIGKILL could hit an unrelated process group
      std::lock_guard<std::mutex> lk(mu_);
      auto it = running_.find(alloc_id);
      if (it != running_.end() && it->second.pid == pid) ::kill(-pid, SIGKILL);
    }).detach();
  }

  Options opts_;
  std::mutex mu_;
  std::string token_;
  struct RunningProc {
    pid_t pid = 0;
    int64_t trial_id = -1;  // -1 = aux task (not re-reported)
  };
  std::map<std::string, RunningProc> running_;
};

}  // namespace dtpu

// TPU chip enumeration (reference agent/internal/detect/: nvidia-smi for
// cuda slots).  libtpu reaches a chip through one of two kinds of device
// node, and a host has one kind or the other:
//   /dev/accel<N>        the accel driver's nodes (older TPU VM images);
//   /dev/vfio/<group>    vfio-bound chips — what the v5e machines this repo
//                        is measured on have, and the only kind they have.
// A vfio group can just as well be a passed-through NIC or GPU, so a group
// counts as a chip only on evidence: its PCI device carries Google's vendor
// id in sysfs, or — where sysfs shows no PCI devices at all (sealed VMs) —
// the host declares itself a TPU VM through libtpu's own environment
// (TPU_ACCELERATOR_TYPE / TPU_CHIPS_PER_HOST_BOUNDS, which the TPU VM
// image sets).  --slots overrides all of it (tests, CPU hosts).
static bool is_number(const std::string& s) {
  return !s.empty() && std::all_of(s.begin(), s.end(),
                                   [](unsigned char c) { return std::isdigit(c); });
}

// 1 = a Google (0x1ae0) device sits in this iommu group, 0 = only other
// vendors' devices do, -1 = sysfs says nothing about the group
static int vfio_group_is_google(const std::string& group) {
  namespace fs = std::filesystem;
  std::error_code ec;
  int verdict = -1;
  fs::path devices = fs::path("/sys/kernel/iommu_groups") / group / "devices";
  for (fs::directory_iterator it(devices, ec), end; !ec && it != end; it.increment(ec)) {
    std::ifstream f(it->path() / "vendor");
    std::string vendor;
    if (!(f >> vendor)) continue;
    if (vendor == "0x1ae0") return 1;
    verdict = 0;
  }
  return verdict;
}

static int detect_slots(std::string* slot_type) {
  namespace fs = std::filesystem;
  int n = 0;
  for (int i = 0; i < 16; ++i) {
    if (fs::exists("/dev/accel" + std::to_string(i))) ++n;
  }
  if (n == 0) {
    const bool tpu_vm = std::getenv("TPU_ACCELERATOR_TYPE") != nullptr ||
                        std::getenv("TPU_CHIPS_PER_HOST_BOUNDS") != nullptr;
    std::error_code ec;
    for (fs::directory_iterator it("/dev/vfio", ec), end; !ec && it != end;
         it.increment(ec)) {
      const std::string group = it->path().filename().string();
      if (!is_number(group)) continue;  // /dev/vfio/vfio is the container node
      const int google = vfio_group_is_google(group);
      if (google == 1 || (google == -1 && tpu_vm)) ++n;
    }
  }
  if (n > 0) {
    *slot_type = "tpu";
    return n;
  }
  // No quiet CPU agent on what was meant to be a TPU host: a cpu slot takes
  // trials and runs them on the CPU, which looks like a very slow success.
  fprintf(stderr,
          "agent: NO TPU CHIP FOUND (no /dev/accel<N>, no TPU vfio group under "
          "/dev/vfio) and no --slots given: registering ONE slot of type cpu. "
          "Trials placed on it run on the CPU. On a TPU host this detection "
          "cannot see, pass --slots <chips>.\n");
  *slot_type = "cpu";
  return 1;
}

int main(int argc, char** argv) {
  signal(SIGPIPE, SIG_IGN);
  dtpu::Options opts;
  opts.slots = 0;  // 0 = auto-detect below
  for (int i = 1; i < argc; ++i) {
    std::string arg = argv[i];
    auto next = [&](const char* name) -> std::string {
      if (i + 1 >= argc) { fprintf(stderr, "missing value for %s\n", name); exit(2); }
      return argv[++i];
    };
    if (arg == "--master-host") opts.master_host = next("--master-host");
    else if (arg == "--master-port") opts.master_port = std::atoi(next("--master-port").c_str());
    else if (arg == "--id") opts.id = next("--id");
    else if (arg == "--host") opts.advertised_host = next("--host");
    else if (arg == "--pool") opts.pool = next("--pool");
    else if (arg == "--slice-id") opts.slice_id = next("--slice-id");
    else if (arg == "--slots") opts.slots = std::atoi(next("--slots").c_str());
    else if (arg == "--python") opts.python = next("--python");
    else if (arg == "--user") opts.user = next("--user");
    else if (arg == "--password") opts.password = next("--password");
    else if (arg == "--state-dir") opts.state_dir = next("--state-dir");
    else if (arg == "--master-tls") opts.master_tls = true;
    else if (arg == "--master-cert") { opts.master_tls = true; opts.master_cert = next("--master-cert"); }
    else { fprintf(stderr, "unknown arg %s\n", arg.c_str()); return 2; }
  }
  if (opts.slots <= 0) {
    opts.slots = detect_slots(&opts.slot_type);
    fprintf(stderr, "agent %s: detected %d %s slot(s)\n", opts.id.c_str(),
            opts.slots, opts.slot_type.c_str());
  }
  if (opts.master_tls && opts.master_cert.empty()) {
    // this client loads NO system trust roots: TLS without a CA bundle
    // would be verification-free and hide a MITM behind a lock icon
    fprintf(stderr,
            "refusing --master-tls without --master-cert: unverified TLS "
            "is worse than explicit plaintext\n");
    return 2;
  }
  return dtpu::Agent(opts).run();
}
